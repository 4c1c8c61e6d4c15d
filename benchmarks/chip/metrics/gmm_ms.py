"""Device milliseconds per window step of the expert layers' grouped
matmuls: the Pallas `gmm` and `tgmm` kernels, found by their names in
the device trace's op list (`gmm.<n>`, `tgmm.<n>`, as the TPU compiler
names them).

None without a device trace, or for a program that runs no such kernel."""
import re

KERNEL = re.compile(r"t?gmm(\.\d+)?$")


def kernel_seconds(run):
    """The window's seconds in the grouped-matmul kernels, or None."""
    dev = run["device"]
    times = [s for n, s in (dev["ops"] if dev else {}).items()
             if KERNEL.match(n)]
    return sum(times) if times else None


def read(run):
    seconds = kernel_seconds(run)
    return None if seconds is None else 1e3 * seconds / len(run["steps"])
