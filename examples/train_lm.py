"""End-to-end training driver: a ~100M-class model for a few hundred steps
through the full production stack — data pipeline, AdamW, checkpointing,
fault-tolerant driver (with a mid-run simulated crash + restart).

    PYTHONPATH=src python examples/train_lm.py [--arch mamba2_130m] [--steps 200]
"""

import argparse
import json
import shutil
import tempfile

from repro.compile_cache import enable_compile_cache
from repro.launch.train import train_main


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2_130m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--keep-ckpt", default=None, help="checkpoint dir to keep")
    args = ap.parse_args()

    ckpt = args.keep_ckpt or tempfile.mkdtemp(prefix="repro_train_")
    try:
        out = train_main(
            args.arch,
            steps=args.steps,
            batch=args.batch,
            seq=args.seq,
            reduced=True,
            reduced_overrides=dict(d_model=256, n_layers=4, vocab=2048, head_dim=64),
            ckpt_dir=ckpt,
            save_every=max(args.steps // 4, 10),
            lr=1e-3,
        )
        print(json.dumps(out, indent=1, default=str))
        assert out["last_loss"] < out["first_loss"], "loss did not decrease"
        print(
            f"\nloss {out['first_loss']:.3f} -> {out['last_loss']:.3f} over "
            f"{out['steps']} steps ({out['params']/1e6:.1f}M params, "
            f"{out['wall_s']:.1f}s) — checkpoints in {ckpt}"
        )
    finally:
        if args.keep_ckpt is None:
            shutil.rmtree(ckpt, ignore_errors=True)


if __name__ == "__main__":
    main()
