"""Seconds per window step the host waits on each group's grad-step
program inside the executor's `collect` (`collect.wait` spans).

None when the run holds no span of the executor's split of `collect`
(tracing off, or a program from before the split); 0.0 when the split
is there and this part is not."""

SPAN = "collect.wait"
SPLIT = ("collect.wait", "collect.d2h", "collect.sum", "grad_upload")


def read(run):
    spans = run["spans"]
    if not any(s["name"] in SPLIT for s in spans):
        return None
    return (sum(s["dur_s"] for s in spans if s["name"] == SPAN)
            / len(run["steps"]))
