"""CPU-mesh attention-kernel comparison at growing sequence lengths
(interpret mode, no chip involved): our grid-pipelined pallas flash
kernel (the production path: flash_attention_mlt / the `attention`
dispatcher) vs the plain XLA reference at seq 2k/8k/32k, plus its
VMEM-footprint model (the v1 kernel that kept the full KV in VMEM, and
its rows in older BENCH_ATTN_CPU.json files, are gone since PR 32). A
`paged_decode` row compares the
serving engines' page-table-indexed decode kernel
(ops/paged_attention.py) against the gather+dense view it replaces,
including the per-tick HBM-bytes model of the eliminated gather.

On CPU, pallas runs in INTERPRET mode — wall-clock there measures the
interpreter, not the TPU kernel, so the numbers reported are:
- correctness (max |err| vs reference) per kernel per seq;
- XLA-reference wall-clock (a real CPU number, the baseline curve);
- the analytic per-program VMEM bytes against the ~16MB/core budget;
- the analytic per-decode-tick HBM bytes for gather-view vs paged kernel.

Writes one JSON line per row and a summary file (BENCH_ATTN_CPU.json) —
the provenance behind docs/serving.md "Attention kernels" and
docs/training_performance.md "Flash attention in the step". Run via
``make bench-attn``.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

# runnable as `python scripts/bench_attention_cpu.py` / `make bench-attn`
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from mlrun_tpu.ops.attention import (  # noqa: E402
    _flash_fwd_v2,
    _repeat_kv,
    attention_reference,
)

VMEM_BUDGET = 16 * 1024 * 1024  # bytes/core (v4/v5 class)


def vmem_model(d: int, block_q: int, block_k: int,
               dtype_bytes: int = 4) -> int:
    """Per-program VMEM bytes (inputs+outputs+scratch the kernel holds):
    q block + one kv block tile + o/lse + scratch (m/l/acc)."""
    return dtype_bytes * (block_q * d + 2 * block_k * d + block_q * d
                          + block_q * 8 + block_q * (2 + d))


def _ready(out):
    (out[0] if isinstance(out, tuple) else out).block_until_ready()


def timeit(fn, *args, reps: int = 3) -> float:
    _ready(fn(*args))  # warmup/compile
    start = time.perf_counter()
    for _ in range(reps):
        _ready(fn(*args))
    return (time.perf_counter() - start) / reps


def run():
    rows = []
    cases = [
        # (seq, batch, q_heads, kv_heads, d)
        (2048, 1, 4, 2, 64),
        (8192, 1, 2, 1, 64),
        (32768, 1, 1, 1, 64),
    ]
    for seq, b, h, hkv, d in cases:
        key = jax.random.PRNGKey(seq)
        kq, kk, kv_ = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, seq, h, d), jnp.float32) * 0.3
        k = jax.random.normal(kk, (b, seq, hkv, d), jnp.float32) * 0.3
        v = jax.random.normal(kv_, (b, seq, hkv, d), jnp.float32) * 0.3
        ref = jax.jit(attention_reference)(q, k, v)
        ref_ms = timeit(jax.jit(attention_reference), q, k, v) * 1e3
        n_rep = h // hkv
        kr, vr = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)

        bytes_needed = vmem_model(d, 512, 512)
        row = {
            "kernel": "flash_v2", "seq": seq, "heads": h, "d": d,
            "vmem_bytes_per_program": bytes_needed,
            "fits_vmem_budget": bytes_needed < VMEM_BUDGET,
            "ref_xla_cpu_ms": round(ref_ms, 2),
        }
        start = time.perf_counter()
        out, _ = _flash_fwd_v2(q, kr, vr, causal=True, interpret=True)
        out.block_until_ready()
        row["interpret_s"] = round(time.perf_counter() - start, 2)
        row["max_err_vs_reference"] = float(jnp.max(jnp.abs(out - ref)))
        rows.append(row)
        print(json.dumps(row))

    # -- paged decode: the serving hot path ---------------------------------
    # one decode token per slot against a KV page pool, kernel (page-table
    # indexed DMA) vs the gather+dense view the engine used to build per
    # layer per tick
    from mlrun_tpu.ops.paged_attention import (  # noqa: E402
        _paged_decode_call,
        paged_decode_reference,
    )

    slots, page_size, pages_per_slot, hkv, n_rep, d = 4, 128, 16, 2, 2, 64
    max_len = page_size * pages_per_slot
    n_pages = slots * pages_per_slot
    key = jax.random.PRNGKey(7)
    kq, kk, kv_, kt = jax.random.split(key, 4)
    # the kernels take the pool as the engine stores it ([L, P+1, ...])
    # and a layer index: one layer here
    k_pages = jax.random.normal(
        kk, (1, n_pages + 1, page_size, hkv, d), jnp.float32) * 0.3
    v_pages = jax.random.normal(
        kv_, (1, n_pages + 1, page_size, hkv, d), jnp.float32) * 0.3
    q = jax.random.normal(kq, (slots, hkv * n_rep, d), jnp.float32) * 0.5
    table = np.arange(n_pages, dtype=np.int32).reshape(slots, pages_per_slot)
    # slots mid-generation at assorted depths (partial last pages)
    pos = np.asarray([max_len - 1, 700, 131, 5], np.int32)

    dense = jax.jit(functools.partial(paged_decode_reference,
                                      page_size=page_size))
    out_ref = dense(q, k_pages, v_pages, 0, jnp.asarray(table),
                    jnp.asarray(pos))
    out_ref.block_until_ready()
    gather_ms = timeit(dense, q, k_pages, v_pages, 0, jnp.asarray(table),
                       jnp.asarray(pos)) * 1e3

    start = time.perf_counter()
    out_kernel = _paged_decode_call(q, k_pages, v_pages, 0,
                                    jnp.asarray(table), jnp.asarray(pos),
                                    page_size, interpret=True)
    out_kernel.block_until_ready()
    kernel_interp_s = time.perf_counter() - start

    dtype_bytes = 4
    # gather path: the dense [slots, max_len] k+v view materialized per
    # layer per tick; kernel path: each slot's LIVE pages read once
    gather_bytes = 2 * slots * max_len * hkv * d * dtype_bytes
    live_pages = int(sum(-(-(int(p) + 1) // page_size) for p in pos))
    kernel_bytes = 2 * live_pages * page_size * hkv * d * dtype_bytes
    row = {
        "kernel": "paged_decode", "seq": max_len, "heads": hkv * n_rep,
        "d": d, "slots": slots, "page_size": page_size,
        "max_err_vs_reference": float(jnp.max(jnp.abs(out_kernel - out_ref))),
        "interpret_s": round(kernel_interp_s, 2),
        "ref_gather_dense_cpu_ms": round(gather_ms, 2),
        "hbm_bytes_per_tick_per_layer_gather": gather_bytes,
        "hbm_bytes_per_tick_per_layer_kernel": kernel_bytes,
        "hbm_gather_traffic_ratio": round(gather_bytes / kernel_bytes, 2),
        # per-(slot, kv-head, page) program: q group + one k/v page tile +
        # o + m/l/acc scratch — flat in max_len
        "vmem_bytes_per_program": dtype_bytes * (
            n_rep * d * 2 + 2 * page_size * d + n_rep * (2 + d)),
        "fits_vmem_budget": True,
    }
    rows.append(row)
    print(json.dumps(row))

    # -- int8 decode: same kernel, half the page bytes ----------------------
    # the paged-decode kernel over an int8 pool: per-vector dequant scales
    # ride the same page-table-indexed BlockSpecs as the pages and
    # dequantization happens in-register — parity vs the dequant+gather
    # reference on the SAME quantized values is f32-round-off
    from mlrun_tpu.serving.llm import _quantize_kv  # noqa: E402

    k8, ks = _quantize_kv(k_pages)
    v8, vs = _quantize_kv(v_pages)
    dense8 = jax.jit(functools.partial(paged_decode_reference,
                                       page_size=page_size))
    out_ref8 = dense8(q, k8, v8, 0, jnp.asarray(table), jnp.asarray(pos),
                      k_scale=ks, v_scale=vs)
    out_ref8.block_until_ready()
    start = time.perf_counter()
    out_k8 = _paged_decode_call(q, k8, v8, 0, jnp.asarray(table),
                                jnp.asarray(pos), page_size,
                                k_scale=ks, v_scale=vs, interpret=True)
    out_k8.block_until_ready()
    int8_interp_s = time.perf_counter() - start
    # bytes per tick: int8 values + f32 per-vector scales vs the native
    # f32 pages — the capacity win that doubles resident pages per HBM
    kernel_bytes_int8 = 2 * live_pages * page_size * hkv * (d * 1 + 4)
    row = {
        "kernel": "int8_decode", "seq": max_len, "heads": hkv * n_rep,
        "d": d, "slots": slots, "page_size": page_size,
        "max_err_vs_dequant_reference": float(
            jnp.max(jnp.abs(out_k8 - out_ref8))),
        "interpret_s": round(int8_interp_s, 2),
        "hbm_bytes_per_tick_per_layer_native": kernel_bytes,
        "hbm_bytes_per_tick_per_layer_int8": kernel_bytes_int8,
        "page_bytes_ratio_native_over_int8": round(
            kernel_bytes / kernel_bytes_int8, 2),
        "fits_vmem_budget": True,
    }
    rows.append(row)
    print(json.dumps(row))

    # -- paged prefill: a prompt chunk over shared prefix pages in place ----
    # the prefix-hit suffix prefill (serving/paged.py): S query rows attend
    # `base` cached tokens straight through the page table, LSE-merged with
    # the local causal flash over the suffix — vs the dense gathered
    # reference the gather path would seed the batch=1 cache with
    from mlrun_tpu.ops.paged_attention import (  # noqa: E402
        paged_prefill_attention,
    )

    s_chunk, base_pages = 128, 8
    base = base_pages * page_size                  # 1024 cached tokens
    kq2, kl, vl = jax.random.split(jax.random.PRNGKey(11), 3)
    qp = jax.random.normal(kq2, (1, s_chunk, hkv * n_rep, d),
                           jnp.float32) * 0.5
    ids = np.full((pages_per_slot,), -1, np.int32)
    ids[:base_pages] = np.arange(base_pages)
    k_loc = jax.random.normal(kl, (1, max_len, hkv * n_rep, d),
                              jnp.float32) * 0.3
    v_loc = jax.random.normal(vl, (1, max_len, hkv * n_rep, d),
                              jnp.float32) * 0.3
    row_mask = ((jnp.arange(max_len) >= base)
                & (jnp.arange(max_len) < base + s_chunk))
    k_loc = k_loc * row_mask[None, :, None, None]
    v_loc = v_loc * row_mask[None, :, None, None]

    start = time.perf_counter()
    out_pf = paged_prefill_attention(
        qp, k_loc, v_loc, jnp.int32(base), k_pages, v_pages, 0,
        jnp.asarray(ids), jnp.int32(base), page_size=page_size,
        interpret=True)
    out_pf.block_until_ready()
    prefill_interp_s = time.perf_counter() - start

    # reference: dense concat of the gathered prefix + the suffix rows
    k_pre = _repeat_kv(k_pages[0, :base_pages].reshape(
        1, base, hkv, d), n_rep)
    v_pre = _repeat_kv(v_pages[0, :base_pages].reshape(
        1, base, hkv, d), n_rep)
    k_full = jnp.concatenate([k_pre, k_loc[:, base:base + s_chunk]], 1)
    v_full = jnp.concatenate([v_pre, v_loc[:, base:base + s_chunk]], 1)
    ref_pf = attention_reference(
        qp, k_full, v_full, causal=True,
        positions_q=base + jnp.arange(s_chunk),
        positions_k=jnp.arange(base + s_chunk))
    # the per-admission dense seed copy the gather path materializes
    # (k+v, the full max_len window, per layer) vs in-place = nothing
    gather_admission_bytes = 2 * max_len * hkv * d * dtype_bytes
    row = {
        "kernel": "paged_prefill", "seq": max_len, "chunk": s_chunk,
        "cached_prefix_tokens": base, "heads": hkv * n_rep, "d": d,
        "page_size": page_size,
        "max_err_vs_reference": float(
            jnp.max(jnp.abs(out_pf - ref_pf))),
        "interpret_s": round(prefill_interp_s, 2),
        "hbm_bytes_per_admission_per_layer_gather":
            gather_admission_bytes,
        "hbm_bytes_per_admission_per_layer_in_place": 0,
        # per-(kv-head, q-block, page) program: q block + one k/v page
        # tile + o/lse + m/l/acc scratch — flat in prefix length
        "vmem_bytes_per_program": dtype_bytes * (
            s_chunk * n_rep * d * 2 + 2 * page_size * d
            + s_chunk * n_rep * (2 + 8 + d)),
        "fits_vmem_budget": True,
    }
    rows.append(row)
    print(json.dumps(row))

    summary = {
        "metric": "attention_kernel_comparison_cpu",
        "rows": rows,
        # flat in the sequence length, at production head dim
        "v2_vmem_bytes_flat_d128": vmem_model(128, 512, 512),
        "production_path": "flash_attention_mlt -> _flash_fwd_v2 "
                           "(grid-pipelined; KV streamed per block, "
                           "seq bounded by HBM not VMEM)",
        "serving_decode_path": "ops/paged_attention.py kernel — KV read "
                               "through the page table per (slot, "
                               "kv-head, page) grid step; the per-tick "
                               "dense-view gather is eliminated; int8 "
                               "pools dequantize in-register "
                               "(docs/serving.md 'Attention kernels')",
        "serving_prefill_path": "paged prefill kernel — a prompt chunk "
                                "attends cached prefix pages in place "
                                "through the page table, LSE-merged "
                                "with the local causal flash over the "
                                "suffix; the per-admission dense "
                                "gather_prefix_pages seed copy is "
                                "eliminated on the kernel path",
    }
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_ATTN_CPU.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"summary": {k: v for k, v in summary.items()
                                  if k != "rows"}}))


if __name__ == "__main__":
    run()
