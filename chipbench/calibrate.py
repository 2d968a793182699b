"""Readings that the limits of ``correct`` are set from, for one cell, in
one process on the chip (the benchmark's runs never run this).

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --out calibrate_<cell>.json

For each seed it drives the program through priming and the steps the
reference follows, exactly as a run's set-up does, and reads:

* ``program``: the run's own numbers against the reference;
* ``control``: the reference computed in bfloat16 (the nearest precision
  below the configuration's float32), put in the program's place;
* ``half_batch``: the reference with the loss's mean taken over the
  first half of the batch only, put in the program's place;
* ``one_worker`` (several workers only): the reference with the mean
  over the first worker's seeds, as a step that left out the gradient's
  exchange between chips would take it.

A state left unchanged reads 1 on ``update_gap`` by definition and needs
no run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))


def readings(run) -> dict:
    """Every variant's model numbers, and the exact checks, of one run."""
    import jax.numpy as jnp

    from chipbench import checks, datasets, reference
    ds, train = run.s.dataset, run.s.cfg["train"]
    table, labels = datasets.device_tables(run.s.cfg["dataset"], ds.n_nodes)

    def steps(**variant):
        return reference.run_steps(
            train, run.params0, table, labels, run.batches,
            forward=run.s.family.forward, block=run.s.family.BLOCK,
            **variant)
    ref = steps()
    out = {}

    def gaps(losses, grad1, p3):
        return checks.model_gaps(losses, grad1, run.params0, p3, *ref)

    out["program"] = gaps(run.losses, run.grad1, run.p3)
    out["control"] = gaps(*steps(dtype=jnp.bfloat16))
    b = len(run.batches[0]["seeds"])
    out["half_batch"] = gaps(*steps(rows=b // 2))
    if run.s.workers > 1:
        out["one_worker"] = gaps(*steps(rows=run.s.batch))
    exact = {"bad_ids": 0, "bad_masks": 0}
    for batch in run.batches:
        c = checks.check_sample(ds.indptr, ds.indices, batch["seeds"],
                                batch["hops"], batch["masks"])
        exact = {k: exact[k] + c[k] for k in exact}
    exact["dropped"] = run.total_dropped
    out["program"].update(exact)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, as --seed takes them")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    from chipbench import harness
    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX finds no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    wl, cfg = harness.load_cell(args.workload)
    s = harness.build(wl, cfg)
    table = {}
    for seed in (int(x) for x in args.seeds.split(",")):
        t = time.perf_counter()
        run = harness.Run(s, seed)
        run.record()
        run.carry = None
        table[str(seed)] = readings(run)
        harness.log(f"seed {seed}: {json.dumps(table[str(seed)])} "
                    f"({time.perf_counter() - t:.1f} s)")
        Path(args.out).write_text(json.dumps(table, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
