"""Faults a cell can have, planted under the timed path for the tests
(harness.child_main, CPU rehearsal only): each must turn `correct` false."""

from __future__ import annotations


def plant(name: str):
    return {"altered_token": altered_token,
            "state_unchanged": lambda: state_unchanged,
            "half_batch": lambda: half_batch,
            "no_exchange": lambda: no_exchange}[name]()


def altered_token():
    """Served: the third token of every stream altered where the model
    hands it to the HTTP layer."""
    from kubeflow_tpu.serving.llm_runtime import LLMModel

    orig = LLMModel._stream_from

    def patched(self, *a, **k):
        n = 0
        for tok, lp in orig(self, *a, **k):
            if tok is not None:
                n += 1
                if n == 3:
                    tok = int(tok) ^ 1
            yield tok, lp

    LLMModel._stream_from = patched
    return None


def state_unchanged(step):
    """Trained: the step returns the state it was given."""
    import jax
    import jax.numpy as jnp

    def broken(state, batch):
        kept = jax.tree.map(jnp.copy, state)   # the step donates its input
        _, metrics = step(state, batch)
        return kept, metrics

    return broken


def _fed_twice(step, rows):
    """The step on `rows(tokens)` fed twice: exactly the mean over those
    rows alone, at the step's own batch shape."""
    import jax
    import jax.numpy as jnp

    def broken(state, batch):
        toks = batch["tokens"]
        kept = rows(toks)
        return step(state, dict(batch, tokens=jax.device_put(
            jnp.concatenate([kept, kept], axis=0), toks.sharding)))

    return broken


def half_batch(step):
    """Trained: every second row left out, the mean taken over the rest."""
    return _fed_twice(step, lambda toks: toks[0::2])


def no_exchange(step):
    """Trained: the exchange between chips left out: the gradient of the
    first data shard's rows alone (the batch's first half) is what every
    chip applies, as when the reduction across shards is skipped."""
    return _fed_twice(step, lambda toks: toks[: toks.shape[0] // 2])
