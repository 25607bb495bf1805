"""End-to-end driver: train the paper's SRU speech architecture on the
synthetic TIMIT stand-in, with checkpoint/restart, then post-training
quantize and report the error/compression trade-off.

Run: PYTHONPATH=src python examples/train_sru_speech.py [--steps 400]
"""
import argparse
import os
import tempfile

import jax

from repro.core import sru_experiment as X
from repro.launch.compile_cache import use_repo_compile_cache
from repro.models.sru import LAYER_NAMES
from repro.training import checkpoint as ckpt


def main():
    use_repo_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()

    trained = X.train_small_sru(steps=args.steps, verbose=True)
    print(f"baseline val {trained.baseline_val_error:.1f}% "
          f"test {trained.baseline_test_error:.1f}%")

    ckpt_dir = args.ckpt_dir or os.path.join(tempfile.gettempdir(),
                                             "sru_speech_ckpt")
    path = ckpt.save(ckpt_dir, args.steps, trained.params, keep=2)
    print(f"checkpointed to {path}")
    restored, step = ckpt.restore(ckpt_dir, trained.params)
    same = all(bool((jax.numpy.asarray(a) == jax.numpy.asarray(b)).all())
               for a, b in zip(jax.tree.leaves(trained.params),
                               jax.tree.leaves(restored)))
    print(f"restore roundtrip at step {step}: exact={same}")

    print("\npost-training quantization sweep (weights/activations):")
    paper_cfg = X.PAPER_CFG
    for wb, ab in ((8, 16), (4, 16), (4, 8), (2, 16), (2, 8)):
        alloc = {n: (wb, ab) for n in LAYER_NAMES}
        err = trained.val_error(alloc)
        # compression computed on the PAPER-scale model (exact arithmetic)
        from repro.core.quantization import compression_ratio
        cr = compression_ratio(paper_cfg.layer_weight_counts(),
                               {n: wb for n in LAYER_NAMES})
        print(f"  W{wb:2d}/A{ab:2d}: val {err:5.1f}% "
              f"({err-trained.baseline_val_error:+5.1f} pp)  "
              f"paper-model compression {cr:4.1f}x")


if __name__ == "__main__":
    main()
