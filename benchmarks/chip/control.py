"""The control of the comparison that decides ``correct``: the plain rank
test computed in bfloat16, one precision below the float32 the system
states, put in the system's place.  The comparison has to reject it.

Usage, from the root of a checkout (on the chip, at the cell's own size)::

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3

For each seed it draws the cell's data and the first batches of its
traffic (as many as a run checks), answers them with the control, and
prints the compared numbers as one JSON line per seed.  The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def control_sample(cfg: dict, mix: dict, seed: int, overrides: dict | None = None):
    """``(sample, ux, uy, facilities, k)``: the control's answers to the
    first ``check_batches`` batches of the cell's traffic, the users as
    float32 device arrays, and what the comparison needs besides."""
    import jax
    import jax.numpy as jnp

    from chipbench.data import RoadNetwork, facility_user_split
    from chipbench.reference import control_member
    from chipbench.traffic import Traffic

    cfg = {**cfg, **(overrides or {})}
    k = int(cfg["k"])
    net = RoadNetwork(int(cfg["points"]), seed)
    facilities, users = facility_user_split(net.points, int(cfg["facilities"]), seed)
    traffic = Traffic(mix, cfg, seed, facilities, net)
    ux = jax.device_put(jnp.asarray(users[:, 0], jnp.float32))
    uy = jax.device_put(jnp.asarray(users[:, 1], jnp.float32))
    batches = traffic.batches()
    sample = []
    for _ in range(int(cfg["check_batches"])):
        qs = next(batches)
        masks = []
        for q in qs:
            if isinstance(q, int):
                masks.append(control_member(ux, uy, facilities, facilities[q], q, k))
            else:
                masks.append(control_member(ux, uy, facilities, q, None, k))
        sample.append((qs, masks, None))
    return sample, ux, uy, facilities, k


def run_control(workload: str, seed: int, overrides: dict | None = None) -> dict:
    from chipbench.check import compare
    from chipbench.reference import reference_ranks

    _, _, cfg, mix = run.load_cell(workload)
    sample, ux, uy, facilities, k = control_sample(cfg, mix, seed, overrides)
    return compare(sample, ux, uy, facilities, k, reference_ranks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax

    run.use_checkout_cache(jax)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    for seed in args.seeds:
        out = run_control(args.workload, seed)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
