"""Where the per-field tables sit on the chip (models/rows.py: ``hold``
for the training loop, which writes them, and for the scorer).

A default-placed tall narrow table is dimension-0-minor on the TPU and
costs a one-chip step two whole-table copies; the loop holds such tables
lane-padded, the one shape whose default layout is row-major. The
compiled program itself says which it is, and the TPU's compiler is
installed here: these tests compile the loop's own steps for a
DESCRIBED v5e (no chip) and read entry layouts, copies and aliases from
the executable. Plus the CPU side: padding changes no arithmetic, and
where the default is row-major already nothing is padded.
"""

import dataclasses
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from fm_spark_tpu import cli, models, sparse
from fm_spark_tpu.models import rows
from fm_spark_tpu.models.rows import PackedTable
from fm_spark_tpu.ops.scatter import dedup_aux, update_lanes
from fm_spark_tpu.train import TrainConfig
from fm_spark_tpu.utils import device as device_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:        # the benchmark's own count of products
    sys.path.insert(0, ROOT)

FIELDS, BUCKET = 4, 4096
CONFIG = TrainConfig(learning_rate=0.05, lr_schedule="constant",
                     optimizer="sgd", reg_factors=1e-6)


def _spec(family, **over):
    """Config 3's, avazu's and config 5's row widths (65, 4*16+1, 17:
    none a whole number of lanes) at a small bucket."""
    common = dict(num_features=FIELDS * BUCKET, num_fields=FIELDS,
                  bucket=BUCKET, **over)
    if family == "fm":
        return models.FieldFMSpec(rank=64, **common), 1024
    if family == "ffm":
        return models.FieldFFMSpec(rank=16, **common), 256
    if family == "ffm_coalescing":
        # ffm_r16.train's lanes: the write coalesces (ops/scatter
        # .update_lanes) and walks its chunks in a loop that carries
        # the table.
        return models.FieldFFMSpec(rank=16, **common), 8192
    return models.FieldDeepFMSpec(rank=16, mlp_dims=(32, 16), **common), 256


@functools.cache
def _described_chip():
    """A described v5e chip, or the reason none can be described here.
    libtpu writes its logs beside the process unless told not to: the
    variable is set for the description only and put back."""
    from jax.experimental import topologies

    had = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:
        return e
    finally:
        if had is None:
            del os.environ["TPU_LOG_DIR"]


@pytest.fixture
def one_chip():
    """A described v5e chip to compile for. The persistent compile cache
    is off for the one test that asks: what a chipless compile writes
    there cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    chip = _described_chip()
    if isinstance(chip, Exception):
        pytest.skip(f"no v5e:2x2 topology can be described here: {chip}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield chip
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture
def chip_defaults(monkeypatch):
    """The CPU answering the layout question as the chip does (a tall
    table is not row-major by default), so that the walk pads here
    what it pads there and its way back is exercised for real."""
    monkeypatch.setattr(
        rows, "default_is_row_major",
        lambda shape, dtype, device: shape[0] <= shape[1])


def _table_copies(compiled, spec, widths):
    """``copy`` ops whose result has a table's shape."""
    shapes = "|".join(f"{spec.bucket},{w}" for w in widths)
    return len(re.findall(rf"= f32\[(?:{shapes})\]\{{[^}}]*\}} copy\(",
                          compiled.as_text()))


@pytest.mark.parametrize("steps_per_call", [1, 4])
@pytest.mark.parametrize("family", ["fm", "ffm", "ffm_coalescing"])
def test_compiled_step_copies_no_table(one_chip, family, steps_per_call):
    spec, batch = _spec(family)
    if family == "ffm_coalescing":
        assert update_lanes(batch, (BUCKET, 128)) < batch
    lowered = sparse.lower_field_sparse_step(
        spec, CONFIG, batch, steps_per_call, device=one_chip)
    compiled = lowered.compile()

    formats_in = compiled.input_formats[0][0]["vw"]
    formats_out = compiled.output_formats[0]["vw"]
    tables = lowered.args_info[0][0]["vw"]
    assert len(formats_in) == len(formats_out) == len(tables) == FIELDS
    for table, fin, fout in zip(tables, formats_in, formats_out):
        assert table.shape == (BUCKET, 128)             # 65 lanes, padded
        assert fin.layout.major_to_minor == (0, 1)      # dimension 1 minor
        assert fout.layout == fin.layout
    assert _table_copies(compiled, spec, (spec.table_width, 128)) == 0
    table_bytes = FIELDS * BUCKET * spec.table_width * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= table_bytes

    if family == "ffm_coalescing":
        return      # the canary below is the two plain families'
    # The same step on tables as spec.init shapes them: if this stops
    # copying, the TPU's default layout changed and the padding (and
    # the assertions above) prove nothing.
    sds = lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=SingleDeviceSharding(one_chip))
    args = jax.tree_util.tree_map(sds, lowered.args_info[0])
    args[0]["vw"] = [sds(t) for t in jax.eval_shape(
        spec.init, jax.random.key(0))["vw"]]
    step = (sparse.make_field_sparse_multistep(spec, CONFIG, steps_per_call)
            if steps_per_call > 1
            else sparse.make_field_ffm_sparse_sgd_step(spec, CONFIG)
            if family.startswith("ffm")
            else sparse.make_field_sparse_sgd_step(spec, CONFIG))
    bare = step.lower(*args).compile()
    assert bare.input_formats[0][0]["vw"][0].layout.major_to_minor == (1, 0)
    copies = _table_copies(bare, spec, (spec.table_width,))
    if copies != 2 * FIELDS:
        pytest.xfail(
            f"a step on default-placed f32[{spec.bucket},"
            f"{spec.table_width}] tables copies {copies} of them, not 2 x "
            f"{FIELDS}: re-read PERF.md §5 before trusting the padding")


# The one-chip training cells' steps as they lower for a v5e (sha256 of
# ``lower_field_sparse_step(...).as_text()`` at the cell's bucket and the
# registry's batch), read on PR 39's tree by PR 40, which moved the FFM
# AdaGrad body's chunked read-rule-write into a function of its own, the
# DLRM stacks into a module of their own and gave ``synthetic_ctr`` its
# bags, and had to leave these five programs as they were. A PR that
# changes one of these steps on purpose reads the new digest here.
LOWERED_STEPS = {
    ("criteo1tb_fm_r64", 1 << 18):
        "621605826bf63c1ddf6d4879b2ab0da2250a091b5d93780cf2b0db34ba242094",
    ("avazu_ffm_r16", 1 << 17):
        "9067935d967aeb0839c9747c683a45c9911ecfa65628140f7d81bc70a5fa2538",
    ("avazu_ffm_r16_adagrad", 1 << 17):
        "b4698054e55164b78721201408eda38db0a9d372aa4219625e6416b0a589bcf3",
    ("criteo1tb_deepfm", 1 << 18):
        "08379ad786dec5b4a5d4f3f1a538bf7126124f1155928689258096def59fc0ce",
    ("criteo1tb_dlrm_mlperf", 1 << 19):
        "2f0d9edbc01e5d67f6916680b2c6659952ab3fe61bb84e7b8fbb9c3e7be7ac9a",
}


@pytest.mark.parametrize("name,bucket", sorted(LOWERED_STEPS))
def test_the_cells_steps_lower_to_the_programs_they_were(one_chip, name,
                                                         bucket):
    import hashlib

    from fm_spark_tpu import configs

    cfg = dataclasses.replace(configs.CONFIGS[name], bucket=bucket)
    text = sparse.lower_field_sparse_step(
        cfg.spec(), cfg.train_config(), cfg.batch_size,
        device=one_chip).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED_STEPS[
        name, bucket]


def _pooled_step(chip, batch=2048, bucket=4096, hots=(3, 1, 100)):
    """The FieldDCN AdaGrad step at a small bucket and batch and a bag of
    100 ids, lowered for ``chip``: ``(spec, lowered)``."""
    from fm_spark_tpu import configs

    cfg = dataclasses.replace(
        configs.CONFIGS["criteo1tb_dcnv2_multihot"], bucket=bucket,
        batch_size=batch, hots=hots, num_fields=13 + sum(hots))
    spec = cfg.spec()
    return spec, sparse.lower_field_sparse_step(
        spec, cfg.train_config(), batch, device=chip)


def test_the_pooled_step_holds_no_row_for_every_id_of_a_bag(one_chip):
    """The FieldDCN AdaGrad step compiled for a v5e at a bag of 100 ids:
    no ``[B x 100, rank]`` block, forward (the pool adds a bag's positions
    as they arrive) or backward (the coalesced totals gather from the
    ``[B, rank]`` pullback through the sort's permutation); tables and
    accumulator tables held as they are (a row is a lane tile), updated in
    place, none copied."""
    batch, bucket = 2048, 4096
    spec, lowered = _pooled_step(one_chip, batch, bucket)
    tables = lowered.args_info[0][0]["vw"]
    slots = lowered.args_info[0][1]["vw"]["n"]
    assert [t.shape for t in tables] == [s.shape for s in slots] == (
        3 * [(bucket, 128)])
    compiled = lowered.compile()
    text = compiled.as_text()
    assert f"[{batch * 100},128]" not in text
    assert f"[{batch},128]" in text
    assert _table_copies(compiled, spec, (128,)) == 0
    table_bytes = 2 * 3 * bucket * 128 * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= table_bytes


def test_the_pooled_write_adds_no_product(one_chip):
    """A bag's write counts each (example, id) pair once and scales its
    example's row by the pair's lanes, elementwise: the step lowers the
    products the DCN cell's driver allows (three a layer less the first
    layer's input gradient, and up to one a cross layer made again), and
    their operations are still what the benchmark counts. A column of
    bags sorts its lanes three times (ids, pairs, unique ids), a column
    of one id a bag twice."""
    from benchmark import dcn_flops

    batch = 2048
    spec, lowered = _pooled_step(one_chip, batch)
    text = lowered.as_text()
    products = (len(spec.bottom_dims) + len(spec.top_dims) - 2
                + 2 * spec.cross_layers)
    stated = [line for line in text.splitlines() if "dot_general" in line]
    assert (3 * products - 1 <= len(stated)
            <= 3 * products - 1 + spec.cross_layers)
    assert spec.mxu_flops_per_step(batch) == dcn_flops.step_matmul_flops(
        batch, spec.bottom_dims, spec.top_dims, spec.cross_layers,
        spec.cross_rank)
    assert len(re.findall(r'"stablehlo\.sort"\(', text)) == sum(
        3 if h > 1 else 2 for h in spec.hots)


# ops/scatter.update_lanes leaves a large write plain only where XLA
# gives the plain add its cheap lowering, the one that sorts the update
# first (PERF.md §6, PR 37: 14-41 ns a lane with the sort, 75-115
# without). Where that starts is the compiler's choice, read here from
# the compiled add itself: a libtpu that moves it fails these cases, not
# a cell.
@pytest.mark.parametrize("table,over", [
    ((1 << 19, 128), 0), ((1 << 19, 128), 1),       # DLRM's
    ((1 << 18, 128), 0),                            # config 3's and 5's
    ((1 << 17, 384), 0), ((1 << 17, 384), 1),       # config 4's
])
def test_xla_sorts_the_plain_add_where_update_lanes_says_it_is_cheap(
        one_chip, table, over):
    """No ``sort`` at the share of the rows ``update_lanes`` calls dear,
    one a lane over it."""
    from fm_spark_tpu.ops import scatter

    share = table[0] // scatter.PLAIN_DEAR_ROWS_PER_LANE
    lanes = share + over
    chip = SingleDeviceSharding(one_chip)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=chip)
    compiled = jax.jit(
        lambda t, i, d: t.at[i].add(d, mode="drop"), donate_argnums=0).lower(
            sds(table, jnp.float32), sds((lanes,), jnp.int32),
            sds((lanes, table[1]), jnp.float32)).compile()
    sorts = len(re.findall(r" sort\(", compiled.as_text()))
    assert sorts == over
    # The rule's side of it, in whole chunks: coalesced up to the share,
    # plain from one chunk over it (past the lanes the lane clause takes).
    assert update_lanes(share, table) == scatter.RULE_CHUNK
    over_it = max(share, scatter.COALESCE_MAX_LANES) + scatter.RULE_CHUNK
    assert update_lanes(over_it, table) == over_it


# ops/scatter.coalesce sorts a field's ids ONCE, keys and permutation
# together: ``jnp.argsort`` is that two-operand sort with the sorted
# keys thrown away, and ``ids[order]`` after it compiled to XLA's gather
# custom call over B scalars, 7.1 ns a lane on the v5e, 7-18% of four
# training cells' steps (PERF.md §6, PR 39). The second sort brings the
# unique ids to the front.
@pytest.mark.parametrize("lanes,width", [
    (8192, 369),        # ffm_r16.train's and ffm_r16_adagrad.train's
    (16384, 17),        # deepfm_r16.train's
    (55296, 128),       # dlrm_e128.train's
])
def test_coalesce_sorts_twice_and_gathers_no_ids(one_chip, lanes, width):
    from fm_spark_tpu.ops import scatter

    chip = SingleDeviceSharding(one_chip)
    text = jax.jit(scatter.coalesce).lower(
        jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=chip),
        jax.ShapeDtypeStruct((lanes, width), jnp.float32, sharding=chip),
    ).compile().as_text()
    assert len(re.findall(r" sort\(", text)) == 2
    id_gathers = re.findall(
        rf"= s32\[{lanes}\]\S* fusion\([^\n]*kind=kCustom[^\n]*", text)
    assert not id_gathers, id_gathers


# The xDeepFM CIN holds its maps with the pair (d, example) on the lanes
# (models/field_xdeepfm.py): a Hadamard block laid out ``[B D, H, m]``
# puts its m fields on the lanes, and the compiler copies every such
# block into the product's layout, forward and for the kernel's gradient
# (six of 1.28 GB a step at xdeepfm_cin200.train's sizes, PERF.md §6).
# The CIN's forward and pullback compiled alone, at 13 fields of 8, 256
# examples and 16 maps a layer.
def _cin_program(chip, cin, m=13, d=8, batch=256, maps=(16, 16, 16)):
    """``(spec, batch, lowered, compiled)``: ``cin(spec, kernels, x0)``
    and its pullback, for ``chip``."""
    from fm_spark_tpu import configs

    spec = dataclasses.replace(
        configs.CONFIGS["criteo_xdeepfm_cin200"], num_fields=m, rank=d,
        bucket=64, cin_layers=maps).spec()
    chip = SingleDeviceSharding(chip)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=chip)
    kernels = [sds((h, h_prev, m)) for h_prev, h in
               zip(spec.cin_dims[:-1], spec.cin_dims[1:])]

    def forward_and_pullback(kernels, x0, g):
        p, pullback = jax.vjp(functools.partial(cin, spec), kernels, x0)
        return p, pullback(g)

    lowered = jax.jit(forward_and_pullback).lower(
        kernels, sds((batch * d, m)), sds((batch, sum(maps))))
    return spec, batch, lowered, lowered.compile()


def _block_relays(spec, batch, compiled):
    """``copy`` / ``transpose`` ops as large as the CIN's smallest
    Hadamard block, ``B D m min(H_k)``."""
    least = batch * spec.rank * spec.num_fields * min(spec.cin_dims)
    return [shape for shape in re.findall(
        r"= f32\[([\d,]+)\]\{[^}]*\} (?:copy|transpose)\(",
        compiled.as_text())
        if np.prod([int(n) for n in shape.split(",")]) >= least]


def test_the_cin_relays_at_most_one_block_a_layer(one_chip):
    from fm_spark_tpu.models.field_xdeepfm import FieldXDeepFMSpec

    spec, batch, lowered, compiled = _cin_program(one_chip,
                                                  FieldXDeepFMSpec.cin)
    layers = len(spec.cin_layers)
    stated = re.findall(r"dot_general.*precision = \[(\w+), (\w+)\]",
                        lowered.as_text())
    assert len(stated) == 3 * layers
    assert set(stated) == {("HIGHEST", "HIGHEST")}
    assert len(_block_relays(spec, batch, compiled)) <= layers
    # The guard bites: the same CIN with its maps [B D, H_k] (the
    # check's own copy, xdeep_faults._cin_fn) relays more.
    import importlib.util

    found = importlib.util.spec_from_file_location(
        "xdeep_faults", os.path.join(ROOT, "benchmark", "tests",
                                     "xdeep_faults.py"))
    faults = importlib.util.module_from_spec(found)
    found.loader.exec_module(faults)
    spec, batch, _, before = _cin_program(one_chip, faults._cin_fn())
    assert len(_block_relays(spec, batch, before)) > layers


def _held_blocks(compiled, least):
    """Shapes of the arrays of at least ``least`` elements the compiled
    program holds: results of its entry computation, views and arguments
    left out."""
    entry = compiled.as_text().split("\nENTRY", 1)[1]
    return [shape for shape, op in re.findall(
        r"= f32\[([\d,]+)\]\{[^}]*\} ([\w-]+)\(", entry)
        if op not in ("bitcast", "parameter")
        and np.prod([int(n) for n in shape.split(",")]) >= least]


# The CIN's last layer pools over D before its compression
# (models/field_xdeepfm.py), so its block is never formed at D B columns,
# forward or back; at the cell's sizes only a layer below holds one
# (layer 2's gradient). At _cin_program's sizes the compiler holds no
# layer's block, and the form that builds the last layer's holds one.
@pytest.mark.parametrize("maps", [(16,), (16, 16), (16, 16, 16)])
def test_the_cins_last_layer_holds_no_block(one_chip, maps):
    from fm_spark_tpu.models.field_xdeepfm import FieldXDeepFMSpec

    spec, batch, lowered, compiled = _cin_program(
        one_chip, FieldXDeepFMSpec.cin, maps=maps)
    stated = re.findall(r"dot_general.*precision = \[(\w+), (\w+)\]",
                        lowered.as_text())
    assert len(stated) == 3 * len(maps)
    assert set(stated) == {("HIGHEST", "HIGHEST")}
    # The last layer's block at D B columns, B D m H_{K-1} elements.
    last = batch * spec.rank * spec.num_fields * spec.cin_dims[-2]
    assert not _held_blocks(compiled, last)
    # The guard bites: every layer built at D B columns, the last too.
    import importlib.util

    found = importlib.util.spec_from_file_location(
        "xdeepfm_tests", os.path.join(ROOT, "tests", "test_field_xdeepfm.py"))
    tests = importlib.util.module_from_spec(found)
    found.loader.exec_module(tests)
    _, _, _, before = _cin_program(one_chip, tests.cin_layer_by_layer,
                                   maps=maps)
    assert _held_blocks(before, last)


# The scorer's side (a holder that only reads): the tables as PredictEngine holds a
# generation of each registry family at the sizes the benchmark serves or
# trains, the engine's own program (``spec.predict`` under jit) compiled
# at a small batch bucket. Beside the other described compiles because
# one process loads libtpu (tests/test_serve_layout.py has the CPU side).
SERVED = {
    "fm_65": (lambda: models.FieldFMSpec(
        num_features=39 * 2 ** 19, num_fields=39, bucket=2 ** 19, rank=64),
        "packed", (2 ** 18, 128)),              # config 3 as the cell serves it
    "fm_64_and_w": (lambda: models.FieldFMSpec(
        num_features=8 * 2 ** 19, num_fields=8, bucket=2 ** 19, rank=64,
        fused_linear=False), "packed", (2 ** 18, 128)),
    "ffm_369": (lambda: models.FieldFFMSpec(
        num_features=23 * 2 ** 17, num_fields=23, bucket=2 ** 17, rank=16),
        "padded", (2 ** 17, 384)),
    "deepfm_17": (lambda: models.FieldDeepFMSpec(
        num_features=39 * 2 ** 18, num_fields=39, bucket=2 ** 18, rank=16,
        mlp_dims=(400, 400, 400)), "packed", (2 ** 15, 128)),
}


@pytest.mark.parametrize("case", SERVED)
def test_served_predict_program_moves_no_table(one_chip, case):
    make, form, held_shape = SERVED[case]
    spec = make()
    chip = SingleDeviceSharding(one_chip)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=chip)
    canonical = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                             jax.eval_shape(spec.init, jax.random.key(0)))
    served, shapes, held = rows.hold(canonical, spec.row_tables,
                                     writes=False)
    assert held[f"tables_{form}"] == spec.num_fields
    assert shapes == canonical
    key = spec.row_tables[0]
    for table in served[key]:
        assert (table.lines if form == "packed" else table).shape == held_shape
        assert isinstance(table, PackedTable) == (form == "packed")

    def compiled(tree, bucket=8):
        batch = (bucket, spec.num_fields)
        return jax.jit(spec.predict).lower(
            tree, sds(batch, jnp.int32), sds(batch, jnp.float32)).compile()

    def moved(program):
        """Ops that write a whole table anew: a ``copy``, ``transpose``
        or ``fusion`` whose result has a table's shape, canonical or as
        served. (A ``copy-start`` / ``copy-done`` pair is the compiler
        prefetching lines into fast memory in the layout they have.)"""
        shapes = "|".join(f"{r},{w}" for r, w in
                          {held_shape, canonical[key][0].shape})
        return re.findall(
            rf"= \w+\[(?:{shapes})\]\{{[^}}]*\}} (?:copy|transpose|fusion)\(",
            program.as_text())

    program, bare = compiled(served), compiled(canonical)
    assert moved(program) == []
    for fin in jax.tree.leaves(program.input_formats[0][0][key]):
        assert fin.layout.major_to_minor in ((0, 1), (0,))
    served_bytes = program.memory_analysis().argument_size_in_bytes
    canonical_bytes = bare.memory_analysis().argument_size_in_bytes
    assert served_bytes <= 1.05 * canonical_bytes
    if case == "fm_65":
        # 65 columns stop being stored in 72 sublanes: 10% fewer bytes.
        assert served_bytes <= 0.91 * canonical_bytes
    # The canonical program is the one that copies: if it stops, the
    # TPU's default layout changed and the form proves nothing.
    if len(moved(bare)) != spec.num_fields:
        pytest.xfail(
            f"predict on default-placed {canonical[key][0].shape} tables "
            f"moves {len(moved(bare))} of them, not {spec.num_fields}: "
            "re-read PERF.md §5 before trusting the serving form")


def _held_for_training(tree, consume=True):
    """The training loop's call of the walk (cli._place_field_state)."""
    return rows.hold(tree, sparse.FUSED_TABLE_KEYS, writes=True,
                     consume=consume)


def test_padding_follows_the_devices_default(one_chip):
    def tree(sharding=None):
        sds = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
        return {"w0": sds((), jnp.float32),
                "vw": [sds((BUCKET, 65), jnp.float32),      # config 3
                       sds((BUCKET, 369), jnp.float32),     # avazu
                       sds((BUCKET, 17), jnp.bfloat16),     # config 5
                       sds((BUCKET, 128), jnp.float32),     # whole lanes
                       sds((65, BUCKET), jnp.float32)],     # wider than tall
                "v": [sds((BUCKET, 64), jnp.float32)],      # not the loop's
                "mlp": [sds((64, 32), jnp.float32)]}

    on_chip = tree(SingleDeviceSharding(one_chip))
    padded, shapes, report = _held_for_training(on_chip)
    assert [t.shape for t in padded["vw"]] == [
        (BUCKET, 128), (BUCKET, 384), (BUCKET, 128), (BUCKET, 128),
        (65, BUCKET)]
    assert padded["v"][0].shape == (BUCKET, 64)
    assert padded["mlp"][0].shape == (64, 32)
    assert shapes == on_chip
    assert (report["tables_padded"], report["tables_as_is"],
            report["tables_packed"]) == (3, 2, 0)
    # The CPU lays every table out row-major: nothing to pad, and the
    # way back is the identity.
    same, shapes, _ = _held_for_training(tree())
    assert same == tree() and rows.canonical(same, shapes) == tree()


def test_way_back_cuts_what_the_walk_padded_and_nothing_else(chip_defaults):
    """The way back is the inverse of what was done, by the shapes the
    walk recorded: a ``[w, N]`` table (never padded, far wider than the
    model's w) and a whole-lane table come back whole; the tables passed
    in are consumed one by one."""
    rng = np.random.default_rng(0)
    table_shapes = [(BUCKET, 65), (BUCKET, 369), (BUCKET, 128), (65, BUCKET)]
    host = [rng.random(s, np.float32) for s in table_shapes]
    tree = {"w0": jnp.float32(0.5), "vw": [jnp.asarray(t) for t in host]}
    padded, shapes, _ = _held_for_training(tree)
    assert [t.shape for t in padded["vw"]] == [
        (BUCKET, 128), (BUCKET, 384), (BUCKET, 128), (65, BUCKET)]
    assert [t.is_deleted() for t in tree["vw"]] == [True, True, False,
                                                    False]
    assert not np.asarray(padded["vw"][0][:, 65:]).any()
    back = rows.canonical(padded, shapes)
    assert back["w0"] is padded["w0"]
    for got, want in zip(back["vw"], host):
        np.testing.assert_array_equal(np.asarray(got), want)
    assert not any(t.is_deleted() for t in padded["vw"])    # kept
    # The loop's last act: the padded tables go as they are cut, the
    # others are handed back as they are.
    back = rows.canonical(padded, shapes, release=True)
    assert [t.is_deleted() for t in padded["vw"]] == [True, True, False,
                                                      False]
    for got, want in zip(back["vw"], host):
        np.testing.assert_array_equal(np.asarray(got), want)


def _batch(spec, batch, seed):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.integers(0, spec.bucket, (batch, spec.num_fields)),
                    jnp.int32),
        jnp.asarray(rng.random((batch, spec.num_fields)), jnp.float32),
        jnp.asarray(rng.integers(0, 2, batch), jnp.float32),
        jnp.ones((batch,), jnp.float32),
    )


BF16 = {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"}


@pytest.mark.parametrize("family,spec_over,overrides", [
    ("fm", {}, {}),
    ("fm", {}, {"sparse_update": "dedup"}),
    ("fm", BF16, {"sparse_update": "dedup_sr"}),
    ("fm", {}, {"sparse_update": "dedup", "host_dedup": True}),
    ("fm", BF16, {"sparse_update": "dedup_sr", "host_dedup": True}),
    ("fm", {}, {"sparse_update": "dedup", "compact_device": True,
                "compact_cap": 1024, "gfull_fused": True}),
    ("fm", BF16, {"sparse_update": "dedup_sr", "compact_device": True,
                  "compact_cap": 1024}),
    ("fm", {}, {"sparse_update": "dedup", "compact_device": True,
                "compact_cap": 1024, "fused_embed": "require"}),
    ("ffm", {}, {}),
    ("ffm", {}, {"sparse_update": "dedup", "compact_device": True,
                 "compact_cap": 256}),
    ("deepfm", {}, {}),
    ("deepfm", BF16, {"sparse_update": "dedup_sr", "compact_device": True,
                      "compact_cap": 256}),
])
def test_padded_tables_train_like_canonical(chip_defaults, family,
                                            spec_over, overrides):
    """Two steps on lane-padded tables leave, in the model's columns,
    what two steps on canonical tables leave, and the padding stays
    exactly zero. BITWISE in the dedup modes: their arithmetic runs at
    the model's width and stochastic rounding draws the same bits, only
    the gathers and the writes see the lanes. To a rounding under
    ``scatter_add``, where XLA's scatter adds a row's duplicates in an
    order that may differ with the operand's width."""
    spec, batch = _spec(family, **spec_over)
    config = dataclasses.replace(CONFIG, **overrides)
    rel = 1e-6 if config.sparse_update == "scatter_add" else 0
    step = {"fm": sparse.make_field_sparse_sgd_step,
            "ffm": sparse.make_field_ffm_sparse_sgd_step,
            "deepfm": sparse.make_field_deepfm_sparse_step}[family](
                spec, config)
    want = spec.init(jax.random.key(3))
    got, shapes, _ = _held_for_training(spec.init(jax.random.key(3)))
    state = ((step.init_opt_state(want), step.init_opt_state(got))
             if family == "deepfm" else None)
    for i in range(2):
        b = _batch(spec, batch, seed=i)
        if config.host_dedup:
            b += (tuple(map(jnp.asarray, dedup_aux(np.asarray(b[0])))),)
        if family == "deepfm":
            want, o_want, want_loss = step(want, state[0], jnp.int32(i), *b)
            got, o_got, loss = step(got, state[1], jnp.int32(i), *b)
            state = (o_want, o_got)
        else:
            want, want_loss = step(want, jnp.int32(i), *b)
            got, loss = step(got, jnp.int32(i), *b)
        assert float(loss) == pytest.approx(float(want_loss), rel=rel)
    for t in got["vw"]:
        assert t.shape[1] % rows.LANES == 0
        assert not np.asarray(t[:, spec.table_width:]).any()
    for a, b in zip(jax.tree_util.tree_leaves(rows.canonical(got, shapes)),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=rel * 10, atol=rel * 1e-3)


COMPACT = {"sparse_update": "dedup", "compact_device": True,
           "compact_cap": 1024}


@pytest.mark.parametrize("spec_over,overrides,padded", [
    ({}, {}, False),
    ({}, COMPACT, False),
    ({}, {}, True),
    ({}, COMPACT, True),
    ({"fused_linear": False}, {}, True),
])
def test_placed_steps_match_unplaced(request, spec_over, overrides, padded):
    """Two steps through the single-chip branch of _place_field_state,
    then ``to_canonical`` (a mid-run eval or save, then the return,
    which releases the loop's tables), give the parameters of the
    unplaced step: bitwise where nothing is padded (the CPU's own
    defaults; an unfused spec's ``v`` / ``w`` anywhere, which its body
    neither cuts on read nor pads on write), to a rounding in the
    model's columns where the tables are."""
    if padded:
        request.getfixturevalue("chip_defaults")
    spec, batch = _spec("fm", **spec_over)
    pads = padded and spec.fused_linear
    config = dataclasses.replace(CONFIG, **overrides)
    step, params, opt, prep, to_canonical, mesh = cli._place_field_state(
        spec, config, cli._FIELD_CAPS["FieldFMSpec"],
        spec.init(jax.random.key(3)), {}, 1, 1, False, 1, False)
    assert mesh is None
    if spec.fused_linear:
        placed = device_lib.placement(params)
        assert placed["table_layouts"] == [[0, 1]]
        width = 128 if pads else spec.table_width
        assert placed["table_device_bytes"] == FIELDS * BUCKET * width * 4
    else:
        assert [t.shape for t in params["v"]] == [(BUCKET, 64)] * FIELDS

    bare = jax.jit(sparse.make_field_sparse_sgd_body(spec, config),
                   donate_argnums=(0,))
    want = spec.init(jax.random.key(3))
    for i in range(2):
        b = _batch(spec, batch, seed=i)
        params, opt, loss = step(params, opt, jnp.int32(i), *prep(b))
        want, want_loss = bare(want, jnp.int32(i), *b)
        assert float(loss) == pytest.approx(float(want_loss),
                                            rel=1e-6 if pads else 0)
    same = (functools.partial(np.testing.assert_allclose, rtol=1e-5,
                              atol=1e-9)
            if pads else np.testing.assert_array_equal)
    for release in (False, True):
        got = to_canonical(params, release=release)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert a.shape == b.shape
            same(np.asarray(a), np.asarray(b))
