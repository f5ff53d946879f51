"""End-to-end training driver: data pipeline -> jitted train step ->
ScALPEL runtime -> checkpoint/restart, on a reduced xLSTM-125M.

    PYTHONPATH=src python examples/train_lm.py                # ~2 min CPU
    PYTHONPATH=src python examples/train_lm.py --steps 300    # longer run
    PYTHONPATH=src python examples/train_lm.py --arch qwen3_14b
    PYTHONPATH=src python examples/train_lm.py --full         # full 125M cfg

Kill it mid-run and start again with the same --ckpt-dir: it resumes from
the latest atomic checkpoint with the counter state (and therefore the
multiplex schedule) intact.
"""
import argparse

from repro.configs import model_config
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import Arch
from repro.optim import OptConfig
from repro.train.loop import TrainLoopConfig, fit


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm_125m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--full", action="store_true",
                    help="use the full assigned config (slow on CPU)")
    ap.add_argument("--ckpt-dir", default="/tmp/scalpel_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--jsonl", default="")
    args = ap.parse_args()

    cfg = model_config(args.arch, smoke=not args.full)
    if not args.full:
        # widen the smoke config toward ~15M params for a meaningful run
        cfg = cfg.replace(d_model=max(cfg.d_model, 256),
                          n_layers=max(cfg.n_layers, 4), vocab=8192)
    arch = Arch(cfg)
    print(f"arch {cfg.name}: {arch.n_params() / 1e6:.1f}M params")

    out = fit(
        arch,
        OptConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps),
        DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                   global_batch=args.batch),
        TrainLoopConfig(
            steps=args.steps, log_every=10,
            ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
            hook_every=10, jsonl_path=args.jsonl or None,
        ),
    )
    print(f"\nfinal loss {out['final_loss']:.4f} "
          f"(first {out['losses'][0]:.4f})")
    st = out["step_stats"]
    print(f"step time: mean {st.mean_s * 1e3:.1f}ms p95 {st.p95_s * 1e3:.1f}ms")
    if out["events"]:
        print("events:", *out["events"], sep="\n  ")
    print()
    print(out["report"])


if __name__ == "__main__":
    main()
