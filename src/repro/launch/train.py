"""Production training launcher: ``--arch <id> --shape train_4k --sharding …``.

On real TPU pods this drives the full job; on this CPU container ``--dry-run``
(the default when no accelerator is present) lowers and compiles the exact
production step (see dryrun.py), while ``--smoke`` runs real steps on a
reduced variant — the same code path end to end.

  PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b --smoke
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --dry-run
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--sharding", default="fsdp_tp",
                    choices=["dp", "fsdp", "fsdp_tp"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="run real steps on the reduced variant (CPU)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--scan-chunk", type=int, default=5,
                    help="steps per compiled lax.scan chunk (0 = all)")
    ap.add_argument("--ssl", action="store_true", default=True)
    args = ap.parse_args()

    if args.smoke:
        from repro.compile_cache import enable_compilation_cache
        enable_compilation_cache()
        _run_smoke(args)
        return
    # Dry-run path: delegate (sets XLA_FLAGS before jax import).
    import subprocess
    import sys
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", args.arch,
           "--shape", args.shape, "--strategy", args.sharding,
           "--mesh", "multi" if args.multi_pod else "single"]
    raise SystemExit(subprocess.call(cmd))


def _run_smoke(args) -> None:
    """Real steps on the reduced variant, through the SAME scan-compiled
    engine the SSL trainers use — one epoch of ``--steps`` synthetic
    batches, compiled in ``--scan-chunk``-step donated scans with
    host→device prefetch."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.ssl_loss import SSLHyper
    from repro.models import transformer as tf
    from repro.optim import adagrad, constant_lr
    from repro.train.engine import Engine, TrainState, lift_step
    from repro.train.train_step import lm_train_step

    cfg = get_config(args.arch).reduced()
    print(f"[smoke] {cfg.name}: {cfg.param_count()/1e6:.2f}M params")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    opt = adagrad()
    hyper = SSLHyper(1e-2, 1e-3, 0.0) if args.ssl else None
    state = TrainState.create(params, opt.init(params), jax.random.PRNGKey(0))
    B, T = 4, 32
    rng = np.random.default_rng(0)

    step_fn = lift_step(
        lambda p, o, batch, lr: lm_train_step(p, o, batch, cfg=cfg,
                                              hyper=hyper, opt=opt, lr=lr))

    def epoch():
        for _ in range(args.steps):
            toks = rng.integers(0, cfg.vocab_size, (B, T + 1),
                                dtype=np.int32)
            batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:],
                     "loss_mask": np.ones((B, T), np.float32),
                     "W": np.ones((1, B, B), np.float32),
                     "seq_labels": np.zeros((1, B), np.int32),
                     "seq_label_mask": np.ones((1, B), np.float32)}
            if cfg.modality_tokens:
                batch["modality_embeds"] = np.zeros(
                    (B, cfg.modality_tokens, cfg.modality_dim), np.float32)
            yield batch

    engine = Engine(step_fn, strategy="sequential",
                    scan_chunk=args.scan_chunk, prefetch=2)
    t0 = time.time()
    res = engine.run(epoch, state=state, n_epochs=1,
                     lr_schedule=constant_lr(1e-3))
    row = res.history[-1]
    dt = time.time() - t0
    print(f"  {args.steps} steps in {dt:.2f}s "
          f"({args.steps / dt:.2f} steps/s, scan_chunk={args.scan_chunk}) "
          f"mean loss={row['loss/total']:.4f}")
    print(f"[smoke] done — global step {int(res.state.step)}, "
          "loss finite and decreasing expected")


if __name__ == "__main__":
    main()
