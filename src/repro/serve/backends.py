"""Model-execution backends for the serving gateway.

The gateway separates *what runs* from *how traffic is shaped*:

* :class:`CNNBackend` (default) serves each region's CURRENT federated
  CNN — the model the region trainer holds right now, which is exactly
  what makes federation staleness visible as served accuracy; one jitted
  argmax-predict per region, compiled once per padded batch width.
* :class:`TransformerBackend` dispatches one-token decode steps through
  :func:`repro.launch.serve.make_serve_step` — the production pjit
  serving path (sharded KV cache, donated between steps) — so the same
  gateway can push transformer traffic.  Requests map to token batches;
  there are no labels, so served accuracy is reported as ``None``.

Backends expose ``predict(model_region, x, samples)`` returning an int
prediction array (or ``None`` when the workload has no ground truth)
and a ``has_labels`` flag; both inputs are padded to the gateway's
geometric batch width so compiled signatures are reused across
dispatches.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


class CNNBackend:
    """Serve each region's live federated model (read-only).

    ``predict`` reads ``trainers[j].params`` AT DISPATCH TIME — never a
    copy taken at construction — so a merge installed between serve
    ticks is immediately visible, and a stale region under
    ``soft_async``/``partial`` federation serves its stale model.
    """

    has_labels = True

    def __init__(self, trainers: List):
        self.trainers = trainers
        self._predict: Dict[int, object] = {}

    def _fn(self, j: int):
        fn = self._predict.get(j)
        if fn is None:
            apply_fn = self.trainers[j].apply_fn
            fn = jax.jit(lambda p, x: jnp.argmax(apply_fn(p, x), -1))
            self._predict[j] = fn
        return fn

    def predict(self, model_region: int, x: np.ndarray,
                samples: np.ndarray) -> Optional[np.ndarray]:
        params = self.trainers[model_region].params
        preds = self._fn(model_region)(params, jnp.asarray(x))
        return np.asarray(jax.block_until_ready(preds))


class TransformerBackend:
    """One-token decode through the pjit ``make_serve_step`` path.

    Builds one jitted step (plus its KV cache) per padded batch width
    on a single-device ``(data, model)`` mesh; caches are threaded
    through successive dispatches of the same width (the donated-buffer
    discipline of the production path).  Request sample ids map to
    vocabulary tokens.
    """

    has_labels = False

    def __init__(self, model_cfg=None, seq_len: int = 64,
                 donate: bool = True, seed: int = 0):
        from repro.configs import get_config
        cfg = model_cfg if model_cfg is not None else (
            get_config("llama3.2-3b").reduced(n_layers=2, d_model=64))
        self.cfg = cfg
        self.seq_len = int(seq_len)
        self.donate = donate
        from repro.compat import make_mesh
        self.mesh = make_mesh((1, 1), ("data", "model"))
        from repro.models import transformer as T
        self.params = T.init_params(cfg, jax.random.PRNGKey(seed))
        self._steps: Dict[int, object] = {}   # padded width -> jitted step
        self._caches: Dict[int, object] = {}  # padded width -> live cache
        self._pos: Dict[int, int] = {}

    def _step(self, b: int):
        step = self._steps.get(b)
        if step is None:
            from repro.configs.shapes import InputShape
            from repro.launch.serve import make_serve_step
            from repro.models import transformer as T
            shape = InputShape(f"serve_b{b}", self.seq_len, b, "decode")
            step, _ = make_serve_step(self.cfg, self.mesh, shape,
                                      donate=self.donate)
            self._steps[b] = step
            self._caches[b] = T.init_cache(self.cfg, b, self.seq_len)
            self._pos[b] = 0
        return step

    def predict(self, model_region: int, x: np.ndarray,
                samples: np.ndarray) -> Optional[np.ndarray]:
        b = len(samples)
        step = self._step(b)
        tokens = jnp.asarray(samples % self.cfg.vocab_size,
                             jnp.int32).reshape(b, 1)
        pos = self._pos[b]
        logits, new_cache = step(self.params, self._caches[b], tokens, pos)
        jax.block_until_ready(logits)
        self._caches[b] = new_cache
        self._pos[b] = (pos + 1) % self.seq_len
        return None
