"""The least time the MXU needs for the head's matrix products of one
step (``benchmark/flops.py`` at the bfloat16 peak of ``peaks.json``) over
the device time the WHOLE head took a step, ``deep_head_ms`` (%): every
fusion under the head's scopes is in the divisor, so a product hidden in
one cannot push the share up. Bound by compute; float32 products cost the
MXU six bfloat16 passes, so the configuration as declared cannot read
over a sixth."""

from benchmark.layer_metrics import deep_head_ms


def read(run):
    head_ms = deep_head_ms.read(run)
    flops = run.log.get("deep_head_flops")
    if not head_ms or not flops or run.peak is None:
        return None
    return 100.0 * flops / run.peak["bf16_flops_per_s"] / (head_ms * 1e-3)
