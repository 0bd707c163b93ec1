#!/usr/bin/env python3
"""Readings that the limit of ``logit_err`` is set from, on the chip.

    python3 chipbench/calibrate.py --config olmo-1b --seeds 12 [--first N]

For each seed, in one process: the weights and ``--requests`` requests of
the served shape are drawn as a run draws them; the program's own compiled
forward (the one a cold invocation calls once restored) serves them; the
plain reference and its control (every matmul operand rounded to float8)
are run over the same weights and tokens.  Each line gives the seed, the
program's ``logit_err`` and the control's, each judged by the run's own
check (``harness.logit_check``, ``harness.correct``) at the
configuration's limit.  The limit lies between the largest program reading
and the smallest control reading (``PERF.md``).

Exits 1 unless the program comes out correct and the control not correct
on every seed.  Needs a TPU, like ``run.py``.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=3_000_000_000)
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from chipbench import harness
    try:
        harness.require_accelerator(1)
        harness.import_program()
    except (harness.NoAccelerator, ImportError) as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    import jax
    import numpy as np

    from chipbench import compare, generator, weights
    from repro.launch.compile_cache import enable_compile_cache
    from repro.nn import spec as nnspec
    from repro.models import get_family
    from repro.serving.instance import ExecutableCache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    man = harness.manifest()
    conf = {c["name"]: c for c in man["configs"]}[args.config]
    config = harness.load_json(os.path.join(ROOT, conf["file"]))
    ref = harness.reference_module(config)
    table = ref.param_table(config)
    cfg = harness.program_config(config)
    harness.check_table(cfg, table)
    specs = get_family(cfg).param_specs(cfg)
    forward = ExecutableCache.get(cfg)
    prog_max, ctrl_min = 0.0, float("inf")
    sound = True
    for k in range(args.seeds):
        seed = args.first + 7919 * k
        t0 = time.perf_counter()
        flat = weights.generate(table, seed)
        tree = nnspec.map_leaves(lambda p, s: flat[p], specs)
        reqs = generator.requests(weights.seed_words(seed), cfg.vocab,
                                  args.requests, 2, 32)
        served = [np.asarray(forward(tree, {"tokens": t})) for t in reqs]
        del tree
        toks = list(reqs)
        prog = compare.logit_error(ref, config, flat, served, toks, block=4)
        ctrl = compare.logit_error(ref, config, flat, served=[
            r for r in compare.reference_logits(ref, config, flat, toks,
                                                block=4, quant=compare.fp8)],
            tokens=toks, block=4)
        del flat
        prog_ok = harness.correct(
            {"logit_err": harness.logit_check(prog, config, len(served))})
        ctrl_ok = harness.correct(
            {"logit_err": harness.logit_check(ctrl, config, len(served))})
        sound = sound and prog_ok and not ctrl_ok
        prog_max, ctrl_min = max(prog_max, prog), min(ctrl_min, ctrl)
        print(json.dumps({"config": args.config, "seed": seed,
                          "program": prog, "program_correct": prog_ok,
                          "control": ctrl, "control_correct": ctrl_ok,
                          "limit": config["limits"]["logit_err"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"config": args.config, "seeds": args.seeds,
                      "program_max": prog_max, "control_min": ctrl_min,
                      "limit": config["limits"]["logit_err"],
                      "control_rejected_on_every_seed": sound}), flush=True)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
