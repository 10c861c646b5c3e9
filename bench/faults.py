"""Faults planted underneath the timed path, to show that the check
catches them: each patches the program's engine class for the process.

    state_unchanged  a decode step returns its KV cache unchanged
    token_altered    every token the engines produce (group prefill,
                     backfill join, decode) is the runner-up, not the best
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


FAULTS = {"state_unchanged": state_unchanged, "token_altered": token_altered}


def plant(name: str) -> None:
    """Plant a fault for the rest of the process."""
    FAULTS[name](setattr)
