"""The least time the MXU needs for one step's CIN products
(``benchmark/cin_flops.py`` at the bfloat16 peak of ``peaks.json``) over
the device time the WHOLE CIN took a step, ``cin_ms`` (%): every fusion
under the CIN's scopes is in the divisor, so a product hidden in one
cannot push the share up. Bound by compute; float32 products cost the MXU
six bfloat16 passes, so the configuration as declared cannot read over a
sixth."""

from benchmark.layer_metrics import cin_ms


def read(run):
    ms = cin_ms.read(run)
    flops = run.log.get("cin_flops")
    if not ms or not flops or run.peak is None:
        return None
    return 100.0 * flops / run.peak["bf16_flops_per_s"] / (ms * 1e-3)
