"""Model-zoo demo: train + decode a reduced variant of every assigned
architecture through the same public API used by the production launcher.

    PYTHONPATH=src python examples/multiarch_demo.py [--arch qwen3-32b]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import setup_compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.models import transformer as T


def run(arch: str):
    cfg = get_config(arch).reduced()
    rng = np.random.default_rng(0)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 64
    if cfg.input_mode == "tokens":
        inputs = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)),
                             jnp.int32)
    else:
        inputs = jnp.asarray(rng.normal(size=(b, s, cfg.d_model)),
                             jnp.float32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    step = jax.jit(T.make_train_step(cfg, lr=1e-3))
    t0 = time.time()
    for i in range(3):
        params, m = step(params, {"inputs": inputs, "labels": labels})
    # decode 4 tokens greedily
    cache = T.init_cache(cfg, b, 64)
    tok = inputs[:, :1] if cfg.input_mode == "tokens" else inputs[:, :1, :]
    toks = []
    for pos in range(4):
        logits, cache = T.serve_step(params, cfg, cache, tok, jnp.int32(pos))
        nxt = jnp.argmax(logits, -1)[:, None]
        toks.append(np.asarray(nxt[0, 0]))
        tok = nxt if cfg.input_mode == "tokens" else jnp.zeros(
            (b, 1, cfg.d_model), jnp.float32)
    full = get_config(arch)
    print(f"{arch:24s} loss={float(m['loss']):6.3f} "
          f"decoded={toks} "
          f"[full: {full.param_count()/1e9:6.1f}B params, "
          f"{full.n_layers}L] ({time.time()-t0:.1f}s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS)
    args = ap.parse_args()
    print(setup_compile_cache())
    for arch in ([args.arch] if args.arch else ARCH_IDS):
        run(arch)


if __name__ == "__main__":
    main()
