#!/usr/bin/env python3
"""One run of one benchmark cell on the chip it is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration and its
traffic mix are named in ``BENCHMARK.json``.  The run makes its weights
and requests from ``--seed``, deploys the function (image, compile, one
record-mode invocation, the mix's warm-up), measures for ``--seconds``,
then checks what the window served against the plain reference.

Earlier lines of standard output are ``<label> <json>`` rows; the last is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, last, ``checks`` (each number compared beside its limit,
also the last lines of standard error).  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window, with ``breakdown``.

Without a TPU, or with fewer chips than the cell asks for, or without the
system under test (``src/repro``) beside it, the run exits non-zero and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True,
                    help="seed of the weights, requests and arrivals")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window, report per-layer metrics")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import harness
    return harness.main(args, t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
