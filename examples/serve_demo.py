"""Batched serving demo: greedy decode with the production serve path.

Runs a reduced architecture through prefill (teacher-forced forward) and
then batched one-token decode steps against the same cache structure the
multi-pod `launch/serve.py` factory shards — i.e. the real serving code
path, minus the mesh.

    PYTHONPATH=src python examples/serve_demo.py --arch deepseek-v2-lite-16b
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import setup_compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.models import transformer as T


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    args = ap.parse_args()
    print(setup_compile_cache())

    cfg = get_config(args.arch).reduced()
    rng = np.random.default_rng(0)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    b, p_len = args.batch, args.prompt_len
    cache_len = p_len + args.gen

    if cfg.input_mode == "tokens":
        prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, p_len)),
                             jnp.int32)
        tok_at = lambda i: prompt[:, i:i + 1]
    else:
        prompt = jnp.asarray(rng.normal(size=(b, p_len, cfg.d_model)),
                             jnp.float32)
        tok_at = lambda i: prompt[:, i:i + 1, :]

    step = jax.jit(T.serve_step, static_argnums=1)
    cache = T.init_cache(cfg, b, cache_len)

    # prefill via repeated decode (the cache-consistency test guarantees
    # this equals the teacher-forced forward)
    t0 = time.time()
    logits = None
    for i in range(p_len):
        logits, cache = step(params, cfg, cache, tok_at(i), jnp.int32(i))
    print(f"[{args.arch}] prefilled {p_len} tokens in {time.time()-t0:.2f}s")

    # greedy generation
    out = []
    tok = jnp.argmax(logits, -1)[:, None]
    t0 = time.time()
    for i in range(p_len, cache_len):
        inp = tok if cfg.input_mode == "tokens" else jnp.zeros(
            (b, 1, cfg.d_model), jnp.float32)
        logits, cache = step(params, cfg, cache, inp, jnp.int32(i))
        tok = jnp.argmax(logits, -1)[:, None]
        out.append(np.asarray(tok[:, 0]))
    dt = time.time() - t0
    gen = np.stack(out, 1)
    print(f"generated {args.gen} tokens x batch {b} in {dt:.2f}s "
          f"({args.gen * b / dt:.1f} tok/s on CPU)")
    print("sequences:")
    for r in range(b):
        print("  ", gen[r].tolist())


if __name__ == "__main__":
    main()
