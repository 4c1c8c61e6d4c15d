"""The grouped matmuls' share of their roofline, %: the least time the
chip could take for the work the `gmm` and `tgmm` kernels did in the
window (the model module's `gmm_flops` and `gmm_bytes`, from the pairs
routed to held experts each step, `moe_routed_pairs`, and the step's
groups), over the kernels' device time (`gmm_ms`).

None without a device trace, without those kernels, or for a program
that does not count its routed pairs."""
import harness
import roofline
from metrics.gmm_ms import kernel_seconds


def read(run):
    seconds = kernel_seconds(run)
    steps = run["steps"]
    if seconds is None or not all("moe_routed_pairs" in s for s in steps):
        return None
    model, cfg = harness.model_of(run["cfg"]), run["cfg"]
    pairs = sum(s["moe_routed_pairs"] for s in steps)
    calls = model.expert_layers(cfg) * sum(s["groups"] for s in steps)
    return 100.0 * roofline.share(model.gmm_flops(cfg, pairs),
                                  model.gmm_bytes(cfg, pairs, calls),
                                  seconds, run["peak"])
