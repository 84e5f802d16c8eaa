"""GPT training with automatic parallelization + elastic checkpointing
(reference: examples/jax/test_gpt.py and benchmark/torch/pp/gpt/).

python examples/jax/train_gpt.py [--steps 20] [--no-tiny]

Runs on the devices JAX reports.  For a virtual mesh take it from the
environment, as the test suite does:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--tiny", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="the tiny smoke config; --no-tiny for GPT-2 small")
    ap.add_argument("--ckpt", default="/tmp/easydist_gpt_ckpt")
    args = ap.parse_args()

    from easydist_tpu import easydist_compile
    from easydist_tpu.jaxfront import make_device_mesh
    from easydist_tpu.models import GPTConfig, make_gpt_train_step
    from easydist_tpu.runtime import run_training

    n = len(jax.devices())
    mesh = make_device_mesh((n // 2, 2) if n >= 4 else (n,),
                            ("dp", "tp") if n >= 4 else ("dp",))

    cfg = GPTConfig.tiny() if args.tiny else GPTConfig()
    step, init_state = make_gpt_train_step(cfg, lr=1e-3)
    compiled = easydist_compile(step, mesh=mesh)

    def data():
        key = jax.random.PRNGKey(0)
        while True:
            key, k1 = jax.random.split(key)
            toks = jax.random.randint(k1, (8, cfg.seq), 0, cfg.vocab)
            yield toks[:, :], toks[:, :]  # predict-same toy objective

    losses = []
    state = run_training(compiled, lambda: init_state(jax.random.PRNGKey(0)),
                         data(), args.ckpt, total_steps=args.steps,
                         checkpoint_every=5,
                         on_step=lambda s, l: losses.append(float(l)))
    if losses:
        print(f"trained {len(losses)} steps; "
              f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    else:
        print(f"checkpoint already at step {args.steps}; nothing to do "
              f"(state restored OK)")


if __name__ == "__main__":
    main()
