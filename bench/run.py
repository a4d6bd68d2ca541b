"""Run one benchmark cell once:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object as the last line of standard output (see
``harness.py``).  Exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for.  The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
"""

import time

_T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402


def _process_age_s() -> float:
    """Seconds since this process started (Linux), so set-up counts the
    interpreter's own start; 0 where it cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    sys.path[:0] = [here, os.path.join(root, "src")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    t_start = time.perf_counter() - _process_age_s()
    import harness

    sys.exit(harness.main(t_start=min(t_start, _T0)))
