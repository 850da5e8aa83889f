"""Greedy-token comparison that knows what an argmax tie is.

Engines that reach the same logits through different accumulation orders
(cached decode vs full recompute, the LSE-merged prefix-hit prefill vs
the monolithic pass) agree to round-off, not bit for bit. On a bf16
model two candidate tokens can sit within one or two bf16 steps of each
other, and then which one wins is decided by that round-off: the streams
part ways at a step where neither is wrong. Exact equality asserted
across such a step fails on one JAX build and passes on the next.
"""

import math

import jax.numpy as jnp
import numpy as np


def assert_greedy_equal_up_to_tie(cfg, params, prompt, got, want,
                                  ulps: float = 2.0):
    """``got`` and ``want`` (greedy continuations of ``prompt``) must be
    equal token for token up to the first step where the plain forward's
    logits of the two candidates lie within ``ulps`` bf16 steps — a tie
    at the model's own resolution. Past a tie the contexts differ, so
    nothing further is compared. A divergence at any wider margin fails."""
    from mlrun_tpu.models.llama import forward

    assert len(got) == len(want), (got, want)
    split = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
                 None)
    if split is None:
        return
    context = list(prompt) + list(want[:split])
    logits = np.asarray(
        forward(cfg, params, jnp.asarray([context], jnp.int32))[0, -1],
        np.float32)
    a, b = float(logits[got[split]]), float(logits[want[split]])
    step = 2.0 ** (math.floor(math.log2(max(abs(a), abs(b), 1e-30))) - 7)
    assert abs(a - b) <= ulps * step, (
        f"greedy streams diverge at step {split} ({got[split]} vs "
        f"{want[split]}) with a logit margin of {abs(a - b):.4f} — "
        f"{abs(a - b) / step:.1f} bf16 steps, not a tie", got, want)


def greedy_reference(cfg, params, prompt, n):
    """``n`` greedy tokens after ``prompt`` by the plain full forward,
    recomputed from scratch at every step (no cache, no padding)."""
    from mlrun_tpu.models.llama import forward

    seq, out = list(prompt), []
    for _ in range(n):
        logits = forward(cfg, params, jnp.asarray([seq], jnp.int32))
        out.append(int(jnp.argmax(logits[0, -1])))
        seq.append(out[-1])
    return out


def record_prefills(engine):
    """Wrap ``engine._prefill``: the returned list grows by ``(token
    shape, prefix_kv given)`` with every dispatch of the prefill program."""
    dispatched, program = [], engine._prefill

    def recording(params, tokens, *args, **kwargs):
        dispatched.append((tuple(tokens.shape), "prefix_kv" in kwargs))
        return program(params, tokens, *args, **kwargs)

    engine._prefill = recording
    return dispatched
