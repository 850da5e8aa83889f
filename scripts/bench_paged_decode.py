"""Time one ``paged_decode`` call at the serve cells' shapes, and check it.

For each cell that runs the kernel (``m7b-serve-chat``,
``nemotron-serve-chat128``, ``laguna-serve-longdoc``) this builds a page
pool of the cell's geometry (all its attention layers, bf16), gives each
live row a context drawn like the cell's traffic (prompt plus a part of
the output), and times a loop of calls that walks the layers, each call
taking the one before's output as its query. It prints one JSON line per
cell: microseconds a call, the live pages a call reads against
``slots x pages_per_slot``, and the largest gap to
``paged_decode_reference`` over six live rows (rows with no request must
come out finite).

Run it on the chip from a checkout's root; the kernel is the checkout's:

    python scripts/bench_paged_decode.py [--block-tokens N] [--out FILE]

``--block-tokens`` sets the positions a block of the page-walking kernel
holds (the module's ``_DECODE_BLOCK_TOKENS``), to compare block sizes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mlrun_tpu.ops import paged_attention as pattn  # noqa: E402

PAGE = 128
HEAD_DIM = 128
# geometry and attention layers of each cell (benchmarks/workloads), its
# live rows a tick (the ledger's tick_rows_mean) and how a row's context
# is drawn: prompt tokens, then up to ``output`` generated ones
CELLS = {
    "m7b-serve-chat": dict(slots=32, heads=32, kv_heads=8, layers=16,
                           max_len=2048, n_pages=512, live=32,
                           prompt="chat", output=128),
    "nemotron-serve-chat128": dict(slots=128, heads=32, kv_heads=2,
                                   layers=3, max_len=1280, n_pages=1280,
                                   live=127, prompt="chat", output=256),
    "laguna-serve-longdoc": dict(slots=32, heads=48, kv_heads=8, layers=2,
                                 max_len=8192, n_pages=2048, live=21,
                                 prompt="longdoc", output=128),
}
CALLS = 64
REPEATS = 7
CHECKED_ROWS = 6


def _contexts(rng, cell) -> np.ndarray:
    n = cell["live"]
    if cell["prompt"] == "chat":
        prompt = np.clip(rng.lognormal(np.log(200), 0.8, n), 32, 1024)
    else:
        prompt = rng.uniform(3072, 7936, n)
    out = rng.uniform(0, cell["output"], n)
    pos = (prompt + out).astype(np.int32)
    return np.minimum(pos, cell["max_len"] - 1)


def _case(seed: int, cell):
    rng = np.random.default_rng(seed)
    slots, pps = cell["slots"], cell["max_len"] // PAGE
    pos = np.zeros(slots, np.int32)
    live = rng.permutation(slots)[:cell["live"]]
    pos[live] = _contexts(rng, cell)
    table = np.full((slots, pps), -1, np.int32)
    free = list(rng.permutation(cell["n_pages"]))
    for row in live:
        for p in range(pos[row] // PAGE + 1):
            table[row, p] = free.pop()
    key = jax.random.PRNGKey(seed)
    shape = (cell["layers"], cell["n_pages"] + 1, PAGE, cell["kv_heads"],
             HEAD_DIM)
    k = jax.random.normal(key, shape, jnp.bfloat16) * 0.3
    v = jax.random.normal(jax.random.fold_in(key, 1), shape,
                          jnp.bfloat16) * 0.3
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (slots, cell["heads"], HEAD_DIM),
                          jnp.bfloat16) * 0.5
    return q, k, v, jnp.asarray(table), jnp.asarray(pos), live


def _timed(q, k, v, table, pos, layers: int) -> float:
    """Microseconds one call takes, the median of REPEATS loops of CALLS
    calls that walk the layers."""

    @jax.jit
    def loop(q, k, v, table, pos):
        def body(i, q):
            return pattn._paged_decode_call(
                q, k, v, i % layers, table, pos, PAGE).astype(q.dtype)
        return jax.lax.fori_loop(0, CALLS, body, q)

    loop(q, k, v, table, pos).block_until_ready()
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        loop(q, k, v, table, pos).block_until_ready()
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) / CALLS * 1e6


def main():
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--block-tokens", type=int, default=0)
    parser.add_argument("--seed", type=int, default=2147483901)
    parser.add_argument("--out", default="")
    args = parser.parse_args()
    if args.block_tokens:
        pattn._DECODE_BLOCK_TOKENS = args.block_tokens
    for name, cell in CELLS.items():
        q, k, v, table, pos, live = _case(args.seed, cell)
        out32 = np.asarray(
            pattn._paged_decode_call(q, k, v, 0, table, pos, PAGE),
            np.float32)
        # the reference repeats the gathered keys to every head: a few
        # rows keep it inside the chip's memory at 8k contexts
        rows = np.sort(live)[:CHECKED_ROWS]
        ref = pattn.paged_decode_reference(q[rows], k, v, 0, table[rows],
                                           pos[rows], PAGE)
        gap = np.abs(out32[rows] - np.asarray(ref, np.float32))
        pages = int(np.sum(np.asarray(pos)[live] // PAGE + 1))
        line = {
            "cell": name, "device": jax.devices()[0].device_kind,
            "us_per_call": _timed(q, k, v, table, pos, cell["layers"]),
            "kv_pages": pages,
            "grid_pages": cell["slots"] * (cell["max_len"] // PAGE),
            "kv_bytes": pages * PAGE * cell["kv_heads"] * HEAD_DIM * 2 * 2,
            "max_abs_gap": float(gap.max()),
            "finite": bool(np.isfinite(out32).all()),
            "block_tokens": args.block_tokens or None,
        }
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as fh:
                fh.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
