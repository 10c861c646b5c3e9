"""Faults planted underneath the timed path, to show that the check
catches them: each patches the program's engine class for the process.

    state_unchanged  a decode step returns its KV cache unchanged
    token_altered    every token the engines produce (group prefill,
                     backfill join, decode) is the runner-up, not the best
    exchange_left_out  on a mesh, the row-parallel projections (wo, w_down)
                     keep only the first chip's part: the sum over the
                     chips of their partial results is left out
"""

import jax
import jax.numpy as jnp
import numpy as np


def _engine():
    from repro.serving.engine import InferenceEngine
    return InferenceEngine


def state_unchanged(patch):
    cls = _engine()
    orig = cls.run_decode

    def run_decode(self, tokens):
        keep = jax.tree.map(jnp.copy, self.cache)
        out = orig(self, tokens)
        self.cache = keep
        return out
    patch(cls, "run_decode", run_decode)


def _runner_up_first(logits):
    x = np.array(logits)
    flat = x.reshape(-1, x.shape[-1])
    second = np.argsort(flat, axis=-1)[:, -2]
    flat[np.arange(len(flat)), second] = flat.max(-1) + 1.0
    return flat.reshape(x.shape)


def token_altered(patch):
    cls = _engine()
    for entry in ("run_prefill", "prefill_row", "run_decode"):
        orig = getattr(cls, entry)

        def altered(self, *a, _orig=orig, **kw):
            return _runner_up_first(_orig(self, *a, **kw))
        patch(cls, entry, altered)


ROW_PARALLEL = {"wo", "w_down"}


def _first_part_only(w, n: int):
    """`w` with every slice of its partitioned axis but the first chip's
    zeroed; `w` as it is where no axis is partitioned."""
    spec = tuple(w.sharding.spec) + (None,) * w.ndim
    for axis in range(w.ndim):
        if spec[axis] is not None:
            keep = jnp.arange(w.shape[axis]) < w.shape[axis] // n
            keep = keep.reshape([-1 if i == axis else 1
                                 for i in range(w.ndim)])
            return jnp.where(keep, w, jnp.zeros((), w.dtype))
    return w


def exchange_left_out(patch):
    cls = _engine()
    orig = cls.__init__

    def init(self, cfg, params, *a, parallel=None, **kw):
        if parallel is not None:
            n = parallel.mesh.devices.size

            def cut(path, w):
                keys = {getattr(k, "key", None) for k in path}
                if keys & ROW_PARALLEL and "scale" not in keys:
                    return _first_part_only(w, n)
                return w
            params = jax.tree_util.tree_map_with_path(cut, params)
        orig(self, cfg, params, *a, parallel=parallel, **kw)
    patch(cls, "__init__", init)


FAULTS = {"state_unchanged": state_unchanged, "token_altered": token_altered,
          "exchange_left_out": exchange_left_out}


def plant(name: str) -> None:
    """Plant a fault for the rest of the process."""
    FAULTS[name](setattr)
