"""Device milliseconds per window step of the executor's gradient
accumulation program (`accumulate_grads` in `core/executor.py`: each
group's float32 token-weighted add, the last one with the division).

None without a device trace, or for a program that has no such
program (one that sums the gradients on the host)."""

PROGRAM = "accumulate_grads"


def read(run):
    dev = run["device"]
    if not dev or not dev["programs"].get(PROGRAM):
        return None
    return 1e3 * dev["programs"][PROGRAM] / len(run["steps"])
