"""Benchmark: decode throughput + HTTP-level TTFT of the native TPU engine.

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N,
"backend": ..., "mfu": ..., "decode": {...}, "http": {...}}``
and also writes the same record to ``BENCH_OUT.json`` next to this file,
so the number survives log-stream truncation.

Two phases, both on the BASELINE.md north star:

1. **Decode core** — batched ``decode_step`` over a paged KV cache, the
   continuous-batching hot loop (output tokens/sec/chip).  On TPU this is
   measured on BOTH attention paths — the Pallas paged kernel and the
   portable gather path — reporting each plus the speedup.  ``mfu`` =
   measured FLOP/s over the chip generation's peak
   (``fusioninfer_tpu.benchmark.mfu``).
2. **HTTP load** — ShareGPT-style mixed-length streaming requests against
   the full OpenAI-compatible server (p50 TTFT + tok/s/chip through the
   real serving stack), via :mod:`fusioninfer_tpu.benchmark.loadgen`,
   with per-request unique prompts and the observed prefix-cache hit rate
   in the record.

The bench runs on the accelerator jax finds, in this one process, or it
fails: there is no probe, no retry schedule and no CPU fallback.
``BENCH_PLATFORM=cpu`` is the explicit CPU smoke CI uses (tiny model,
``decode_throughput_tiny_cpu``); its numbers are not device numbers.
Any leg's exception is recorded in the record AND, on a run that was
not asked to be CPU, makes the exit code non-zero after the record is
printed.  Its redesign into cells is ROADMAP S0.

Env knobs: ``BENCH_PLATFORM=cpu``, ``BENCH_SKIP_HTTP=1`` (decode core
only), ``BENCH_MODEL`` (e.g. ``qwen3-8b+int8``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import statistics
import subprocess
import sys
import time

_HERE = pathlib.Path(__file__).resolve().parent

_CALIBRATION_VERSION = 2  # bump on ANY change to run_calibration's
# measured workload


def run_calibration(jax, on_tpu: bool = False) -> float:
    """Box-speed denominator: GFLOP/s of a FIXED jitted matmul chain
    (512² f32 ×30 on CPU, 2048² bf16 ×16 on TPU), frozen per
    ``_CALIBRATION_VERSION``: the ratio ``decode_value / calibration``
    cancels box-speed drift only across records that ran identical
    calibration code.  The chain is scanned inside ONE jit and fenced
    by a scalar readback, so per-call dispatch stays out of the number.
    ROADMAP S0(b) drops this leg: a ceiling calibrated to a throttled
    box is not a roofline.
    """
    import jax.numpy as jnp

    n, iters = (2048, 16) if on_tpu else (512, 30)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    x = jax.random.normal(jax.random.key(0), (n, n), dtype)

    @jax.jit
    def chain(a):
        def body(c, _):
            c = c @ a
            # renormalize so the chain can't over/underflow; vector cost
            # is negligible beside the n³ matmul
            return c / jnp.maximum(jnp.max(jnp.abs(c)), 1e-6), ()
        c, _ = jax.lax.scan(body, a, None, length=iters)
        return jnp.sum(c.astype(jnp.float32))

    float(chain(x))  # compile + first run
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        float(chain(x))  # scalar readback = real completion
        dt = time.perf_counter() - t0
        best = max(best, iters * 2 * n ** 3 / dt / 1e9)
    return round(best, 2)


def _median_iqr(vals: list[float]) -> dict:
    """Shared dispersion summary: median, sorted reps, IQR and
    IQR/median — one definition so decode and admissions records can
    never silently diverge."""
    vals = sorted(vals)
    med = statistics.median(vals)
    if len(vals) >= 3:
        q = statistics.quantiles(vals, n=4)
        iqr = q[2] - q[0]
    else:
        iqr = 0.0
    return {"median": med, "reps": [round(v, 2) for v in vals],
            "iqr": round(iqr, 2),
            "rel_iqr": round(iqr / med, 4) if med else 0.0}


_DECODE_REPS = 3  # timed windows per decode measurement


def decode_tokens_needed(start: int, warmup: int, steps: int,
                         reps: int = _DECODE_REPS) -> int:
    """Tokens one batch row consumes in ``run_decode`` (context start +
    warmup + timed steps + the token written on the last step).  The ONE
    definition both run_decode's allocation and callers' pool sizing use
    — an exact-fit pool goes stale silently otherwise."""
    return start + warmup + steps * reps + 1


def stratified_lens(batch: int, span_tokens: int, tail: int,
                    base: int = 256) -> list[int]:
    """Per-row context depths for the ragged long-context leg: linear
    strata from ``base`` up to ``span_tokens - tail`` (room for the
    timed window).  ``max(batch - 1, 1)``: a ``batch == 1`` leg
    (BENCH_MODEL debug runs) yields ``[base]`` instead of
    ZeroDivisionError-ing the whole record."""
    return [base + (span_tokens - base - tail) * i // max(batch - 1, 1)
            for i in range(batch)]


def decode_pool_pages(lens: list[int], warmup: int, steps: int,
                      page_size: int, reps: int = _DECODE_REPS) -> int:
    """Exact-fit page-pool size for a ragged ``run_decode``: per-row
    ceil-div of :func:`decode_tokens_needed` plus the allocator's one
    reserved trash page (``CacheConfig.trash_page``)."""
    need = sum(-(-decode_tokens_needed(ln, warmup, steps, reps) // page_size)
               for ln in lens)
    return need + 1


def run_decode(jax, cfg, batch: int, cache_cfg, prefix_len: int,
               warmup: int, steps: int, reps: int = _DECODE_REPS,
               prefix_lens: list[int] | None = None) -> dict:
    """Timed decode: ``reps`` back-to-back windows of ``steps`` steps
    after one warmup, reported as median tokens/sec with the rep values
    and IQR in-record — a single 16-step window made the r4 −25% swing
    unfalsifiable (VERDICT r4 weak #1).

    ``prefix_lens`` (one per batch row) makes the batch RAGGED — the
    continuous-batching production shape, where each slot sits at its
    own context depth.  The gather path always reads (and materializes)
    all ``max_pages_per_seq`` pages per row; the paged kernel reads only
    each row's live pages, so raggedness is exactly where paging earns
    its keep."""
    import jax.numpy as jnp
    import numpy as np

    from fusioninfer_tpu.engine.kv_cache import PageAllocator, init_kv_cache
    from fusioninfer_tpu.engine.model_runner import decode_step

    from fusioninfer_tpu.models.transformer import init_params

    cache_cfg.validate()
    if cfg.quantization == "int8":
        # init on the host CPU and ship int8 only — an 8B bf16 tree would
        # OOM the chip before quantization could shrink it
        from fusioninfer_tpu.models.quantization import quantize_params

        with jax.default_device(jax.devices("cpu")[0]):
            params = quantize_params(cfg, init_params(cfg, jax.random.key(0)))
        params = jax.device_put(params, jax.devices()[0])
    else:
        params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(0))
    cache = init_kv_cache(cfg, cache_cfg)

    starts = prefix_lens if prefix_lens is not None else [prefix_len] * batch
    assert len(starts) == batch
    alloc = PageAllocator(cache_cfg)
    tables = np.zeros((batch, cache_cfg.max_pages_per_seq), np.int32)
    for i in range(batch):
        alloc.allocate(str(i), decode_tokens_needed(starts[i], warmup,
                                                    steps, reps))
        tables[i] = alloc.page_table_row(str(i))
    page_tables = jnp.asarray(tables)
    active = jnp.ones((batch,), bool)
    base_pos = jnp.asarray(starts, jnp.int32)
    rng = np.random.default_rng(0)

    def one_step(cache, off):
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, batch, dtype=np.int32))
        return decode_step(cfg, cache_cfg, params, cache, tokens,
                           base_pos + off, page_tables, active)

    def sync(logits) -> None:
        # a D2H scalar read fences the window (block_until_ready
        # fences too on a directly attached chip — CHANGES.md PR 21).
        # Every step chains through the donated cache, so one scalar
        # from the last logits covers the whole window.
        float(logits[0, 0])

    off = 0
    for _ in range(warmup):
        cache, logits = one_step(cache, off)
        off += 1
    sync(logits)

    vals = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(steps):
            cache, logits = one_step(cache, off)
            off += 1
        sync(logits)
        vals.append(batch * steps / (time.perf_counter() - t0))
    d = _median_iqr(vals)
    return {"tok_s": d["median"], "reps": d["reps"], "iqr": d["iqr"],
            "rel_iqr": d["rel_iqr"], "steps": steps, "n_reps": reps}


def run_admissions(cfg, cache_cfg, max_batch_size: int = 8,
                   n_requests: int = 48, reps: int = 3) -> dict:
    """Admission throughput: drain ``n_requests`` one-token requests
    through a fresh engine — dominated by admission + prefill + slot
    machinery, the series the r4 "+50% admissions/sec" commit claimed
    with no record field to falsify it (VERDICT r4 ask #4)."""
    from fusioninfer_tpu.engine.engine import NativeEngine, Request
    from fusioninfer_tpu.engine.sampler import SamplingParams

    vals = []
    engine = NativeEngine(cfg, cache_cfg=cache_cfg,
                          max_batch_size=max_batch_size)
    # untimed warmup: one full rep-shaped drain, so every jit signature
    # the timed reps hit (padding buckets AND the 1/2/4/8 power-of-two
    # prefill-group sizes that arise as slots free) compiles up front
    warm = [Request(f"w-{i}", [1 + (i % 7), 2, 3 + (i % 5), 4],
                    SamplingParams(max_tokens=1, temperature=0.0))
            for i in range(n_requests)]
    for r in warm:
        engine.add_request(r)
    while engine.has_work():
        engine.step()
    for rep in range(reps):
        reqs = [Request(f"a{rep}-{i}", [1 + (i % 7), 2, 3 + (i % 5), 4],
                        SamplingParams(max_tokens=1, temperature=0.0))
                for i in range(n_requests)]
        for r in reqs:
            engine.add_request(r)
        t0 = time.perf_counter()
        done = 0
        while done < n_requests and engine.has_work():
            done += sum(1 for o in engine.step() if o.finished)
        vals.append(n_requests / (time.perf_counter() - t0))
    d = _median_iqr(vals)
    return {"admissions_per_s": round(d["median"], 2), "reps": d["reps"],
            "iqr": d["iqr"], "rel_iqr": d["rel_iqr"],
            "n_requests": n_requests}


def run_kernel_microbench(jax, on_tpu: bool,
                          calibration_gflops: float | None) -> dict:
    """Raw attention-op microbench with dispersion (same reps/IQR shape
    as the decode legs): the ONE ragged kernel against the portable
    flat-gather baseline at a mixed decode+chunk shape.  Ratios > 1 mean
    the ragged kernel wins; ``mfu_box`` is the ragged leg's attention
    FLOP/s over this box's calibrated matmul ceiling (VERDICT #8).  On
    CPU the kernels run in interpret mode: the ratios there prove the
    leg's plumbing, not kernel performance — the TPU evidence path is
    the real measurement."""
    import jax.numpy as jnp
    import numpy as np

    from fusioninfer_tpu.ops.paged_attention import (
        ragged_paged_attention,
        reference_ragged_paged_attention,
    )

    if on_tpu:
        # serving shapes: Qwen3-1.7B heads, 32 decode rows at ragged
        # ~short contexts + one 512-token chunk row (the fused-step mix)
        KV, G, Hd, ps, mp = 8, 4, 128, 128, 16
        b_dec, chunk, iters = 32, 512, 10
        interpret = False
    else:
        KV, G, Hd, ps, mp = 2, 2, 64, 16, 4
        b_dec, chunk, iters = 4, 24, 2
        interpret = True
    H = KV * G
    reps = 5
    rng = np.random.default_rng(0)
    # decode rows at stratified context depths; one chunk row from 0
    lens = [ps + (ps * (mp - 1) - ps) * i // max(b_dec - 1, 1)
            for i in range(b_dec)]
    R = b_dec + 1
    q_lens = np.array([1] * b_dec + [chunk], np.int32)
    q_begins = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
    starts = np.array(lens + [0], np.int32)
    T = int(q_lens.sum())
    n_pages = int(sum(-(-(l + 1) // ps) for l in lens)
                  + -(-chunk // ps) + 1)
    tables = np.full((R, mp), n_pages - 1, np.int32)
    nxt = 0
    for r in range(R):
        need = -(-int(starts[r] + q_lens[r]) // ps)
        for i in range(min(need, mp)):
            tables[r, i] = nxt
            nxt += 1
    key = jax.random.key(0)
    kq, kk, kv = jax.random.split(key, 3)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    q = jax.random.normal(kq, (T, H, Hd), dt)
    k_pages = jax.random.normal(kk, (KV, n_pages, ps, Hd), dt)
    v_pages = jax.random.normal(kv, (KV, n_pages, ps, Hd), dt)
    tables_d = jnp.asarray(tables)
    starts_d = jnp.asarray(starts)
    q_begins_d = jnp.asarray(q_begins)
    q_lens_d = jnp.asarray(q_lens)

    gather = jax.jit(reference_ragged_paged_attention)

    legs = {
        "ragged": lambda: ragged_paged_attention(
            q, k_pages, v_pages, tables_d, starts_d, q_begins_d, q_lens_d,
            interpret=interpret),
        "gather": lambda: gather(q, k_pages, v_pages, tables_d, starts_d,
                                 q_begins_d, q_lens_d),
    }
    out: dict = {
        "shape": {"kv_heads": KV, "group": G, "head_dim": Hd,
                  "page_size": ps, "decode_rows": b_dec, "chunk": chunk,
                  "flat_tokens": T, "iters": iters,
                  "interpret": interpret},
        "note": ("ragged = one flat ragged kernel (decode rows + chunk "
                 "row, zero padding); gather = portable flat-gather "
                 "baseline.  calls/s medians; interpret=True legs prove "
                 "plumbing, not speed"),
    }
    rates: dict = {}
    for name, fn in legs.items():
        try:
            # compile + one untimed warm window outside the measurement
            # (first post-compile calls still pay allocator/thread
            # warmup; the median absorbs the rest)
            for _ in range(1 + iters):
                o = fn()
            float(jnp.asarray(o, jnp.float32).ravel()[0])
            vals = []
            for _ in range(reps):
                t0 = time.perf_counter()
                for _ in range(iters):
                    o = fn()
                # D2H readback fences the window
                float(jnp.asarray(o, jnp.float32).ravel()[0])
                vals.append(iters / (time.perf_counter() - t0))
            d = _median_iqr(vals)
            out[name] = {"calls_per_s": round(d["median"], 3),
                         "reps": d["reps"], "iqr": d["iqr"],
                         "rel_iqr": d["rel_iqr"]}
            rates[name] = d["median"]
        except Exception as e:
            out[f"{name}_error"] = f"{type(e).__name__}: {str(e)[:400]}"
    if rates.get("ragged") and rates.get("gather"):
        out["ragged_vs_gather"] = round(rates["ragged"] / rates["gather"], 3)
    if rates.get("ragged"):
        # causal attention FLOPs of the REAL tokens only (the ragged
        # kernel's whole point): 4·H·Hd per (token, visible position)
        visible = sum(int(starts[r]) + i + 1
                      for r in range(R) for i in range(int(q_lens[r])))
        flops = 4.0 * H * Hd * visible
        out["attn_gflops_per_call"] = round(flops / 1e9, 4)
        if calibration_gflops:
            out["mfu_box"] = round(
                rates["ragged"] * flops / (calibration_gflops * 1e9), 4)
    try:
        out["longctx"] = run_longctx_stratum(jax, on_tpu)
    except Exception as e:
        out["longctx"] = {"error": f"{type(e).__name__}: {str(e)[:400]}"}
    return out


def run_longctx_stratum(jax, on_tpu: bool, reps: int = 5) -> dict:
    """The flash-decode evidence leg: decode rows at 4k/16k/32k context,
    KV-split page walk vs the serial single walk, with the same
    reps/IQR dispersion shape as every other kernel leg.

    On TPU the legs time the REAL kernels — ``ragged_paged_attention``
    (one sequential page chain per row) against
    ``ragged_paged_attention_kvsplit`` at the full split fan-out.  On
    CPU, Pallas interpret mode serializes grid programs, so timing the
    kernels there would measure the emulator, not the schedule; the CPU
    proxy instead times two jnp implementations of the exact schedules
    — a ``lax.scan`` serial page chain vs the same per-page math with
    ``kv_splits`` page lanes advancing in lockstep plus the log-sum-exp
    combine — which exposes the serialization-vs-parallelism effect the
    split grid exists to remove (the one-page-walk wall).  Ratios > 1
    mean the KV-split schedule wins; the 32k-context ratio is the
    headline ``kvsplit_vs_singlewalk`` the record gate enforces.  A
    small interpret-mode kernel pair additionally pins plumbing +
    split-vs-singlewalk numeric agreement (``kvsplit_kernel_ok``)."""
    import jax.numpy as jnp
    import numpy as np

    from fusioninfer_tpu.ops.paged_attention import (
        KV_SPLIT_CHUNKS,
        ragged_paged_attention,
        ragged_paged_attention_kvsplit,
    )

    S = KV_SPLIT_CHUNKS
    if on_tpu:
        KV, G, Hd, ps, B = 8, 4, 128, 128, 8
        contexts, iters = (4096, 16384, 32768), 10
    else:
        # the CPU proxy's regime is deliberately latency-dominated
        # (MQA row, tiny pages): on the chip a decode page step costs
        # ~fixed DMA+issue latency regardless of page bytes, and the
        # serial chain is the wall — here the scan step's fixed
        # dispatch cost models that latency, so the 8-lane walk's
        # step-count reduction is the same effect the split grid buys
        KV, G, Hd, ps, B = 1, 4, 32, 8, 1
        contexts, iters = (4096, 16384, 32768), 6
    H = KV * G
    out: dict = {
        "shape": {"kv_heads": KV, "group": G, "head_dim": Hd,
                  "page_size": ps, "decode_rows": B, "kv_splits": S,
                  "iters": iters,
                  "proxy": "pallas-hw" if on_tpu else "jnp-schedule"},
        "note": ("kvsplit_vs_singlewalk per context depth; CPU times one "
                 "jnp walk at lane width 1 vs KV_SPLIT_CHUNKS (identical "
                 "per-page math + the kernel's LSE combine — interpret "
                 "mode serializes grid programs, so it cannot show the "
                 "schedule), TPU times the real kernels"),
    }

    def timed(fn, result_probe):
        for _ in range(1 + iters):
            o = fn()
        float(jnp.asarray(result_probe(o), jnp.float32).ravel()[0])
        vals = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                o = fn()
            float(jnp.asarray(result_probe(o), jnp.float32).ravel()[0])
            vals.append(iters / (time.perf_counter() - t0))
        d = _median_iqr(vals)
        return {"calls_per_s": round(d["median"], 3), "reps": d["reps"],
                "iqr": d["iqr"], "rel_iqr": d["rel_iqr"]}

    if not on_tpu:
        # ONE jnp walk parameterized by lane width — singlewalk is the
        # same code at lanes=1, so the A/B isolates the SCHEDULE (page
        # steps per lane + the cross-lane LSE combine), never a math
        # difference
        def make_walk(P, lanes):
            steps = P // lanes

            @jax.jit
            def walk(q, kp, vp):
                qg = q.reshape(B, KV, G, Hd)
                ks = kp.reshape(KV, B, steps, lanes * ps, Hd)
                vs_ = vp.reshape(KV, B, steps, lanes, ps, Hd)

                def step(carry, i):
                    m, l, acc = carry
                    s = jnp.einsum("bkgd,kbtd->bkgt", qg,
                                   ks[:, :, i]).reshape(
                                       B, KV, G, lanes, ps)
                    m_c = jnp.max(s, -1, keepdims=True)
                    m_new = jnp.maximum(m, m_c)
                    pexp = jnp.exp(s - m_new)
                    alpha = jnp.exp(m - m_new)
                    l2 = alpha * l + pexp.sum(-1, keepdims=True)
                    pv = jnp.einsum("bkglp,kblpd->bkgld", pexp,
                                    vs_[:, :, i])
                    return (m_new, l2, alpha * acc + pv), None

                init = (jnp.full((B, KV, G, lanes, 1), -jnp.inf),
                        jnp.zeros((B, KV, G, lanes, 1)),
                        jnp.zeros((B, KV, G, lanes, Hd)))
                (m, l, acc), _ = jax.lax.scan(step, init,
                                              jnp.arange(steps))
                # cross-lane combine (the kernel's fixed-order fold)
                state = (m[..., 0, :], l[..., 0, :], acc[..., 0, :])
                for s_ in range(1, lanes):
                    ma, la, aa = state
                    mb, lb, ab = (m[..., s_, :], l[..., s_, :],
                                  acc[..., s_, :])
                    mn = jnp.maximum(ma, mb)
                    al, be = jnp.exp(ma - mn), jnp.exp(mb - mn)
                    state = (mn, al * la + be * lb, al * aa + be * ab)
                m, l, acc = state
                return acc / jnp.maximum(l, 1e-20)

            return walk

    contexts_out: dict = {}
    headline = None
    for ctx in contexts:
        P = ctx // ps
        key = jax.random.key(ctx)
        kq, kk, kv = jax.random.split(key, 3)
        entry: dict = {}
        if on_tpu:
            q = jax.random.normal(kq, (B, H, Hd), jnp.bfloat16)
            kp = jax.random.normal(kk, (KV, B * P + 1, ps, Hd),
                                   jnp.bfloat16)
            vp = jax.random.normal(kv, (KV, B * P + 1, ps, Hd),
                                   jnp.bfloat16)
            tables = jnp.asarray(
                np.arange(B * P, dtype=np.int32).reshape(B, P))
            starts = jnp.full((B,), ctx - 1, jnp.int32)
            qb = jnp.arange(B, dtype=jnp.int32)
            ql = jnp.ones((B,), jnp.int32)
            entry["singlewalk"] = timed(
                lambda: ragged_paged_attention(
                    q, kp, vp, tables, starts, qb, ql), lambda o: o)
            entry["kvsplit"] = timed(
                lambda: ragged_paged_attention_kvsplit(
                    q, kp, vp, tables, starts, qb, ql, kv_splits=S),
                lambda o: o)
        else:
            q = jax.random.normal(kq, (B, H, Hd), jnp.float32)
            kp = jax.random.normal(kk, (KV, B, P, ps, Hd), jnp.float32)
            vp = jax.random.normal(kv, (KV, B, P, ps, Hd), jnp.float32)
            single, split = make_walk(P, 1), make_walk(P, S)
            entry["singlewalk"] = timed(lambda: single(q, kp, vp),
                                        lambda o: o)
            entry["kvsplit"] = timed(lambda: split(q, kp, vp),
                                     lambda o: o)
        ratio = round(entry["kvsplit"]["calls_per_s"]
                      / max(entry["singlewalk"]["calls_per_s"], 1e-9), 3)
        entry["kvsplit_vs_singlewalk"] = ratio
        contexts_out[str(ctx)] = entry
        headline = ratio
    out["contexts"] = contexts_out
    # the gated headline: the deepest (32k) context's ratio
    out["kvsplit_vs_singlewalk"] = headline

    # plumbing + numeric-agreement probe through the REAL kernels at a
    # small interpret-friendly shape (bit-identity across split counts
    # is pinned by the test suite; this keeps the evidence in-record)
    try:
        ps2, P2, B2 = 16, 16, 2
        key = jax.random.key(7)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B2, H, Hd), jnp.float32)
        kp = jax.random.normal(kk, (KV, B2 * P2 + 1, ps2, Hd), jnp.float32)
        vp = jax.random.normal(kv, (KV, B2 * P2 + 1, ps2, Hd), jnp.float32)
        tables = jnp.asarray(
            np.arange(B2 * P2, dtype=np.int32).reshape(B2, P2))
        starts = jnp.full((B2,), ps2 * P2 - 1, jnp.int32)
        qb = jnp.arange(B2, dtype=jnp.int32)
        ql = jnp.ones((B2,), jnp.int32)
        interp = not on_tpu
        base = np.asarray(ragged_paged_attention(
            q, kp, vp, tables, starts, qb, ql, interpret=interp),
            np.float32)
        split = np.asarray(ragged_paged_attention_kvsplit(
            q, kp, vp, tables, starts, qb, ql, kv_splits=S,
            interpret=interp), np.float32)
        out["kvsplit_kernel_ok"] = bool(
            np.allclose(base, split, atol=2e-5, rtol=2e-5))
    except Exception as e:
        out["kvsplit_kernel_error"] = f"{type(e).__name__}: {str(e)[:200]}"
    return out


def model_param_count(cfg) -> int:
    """Analytic parameter count from :class:`ModelConfig` — the same
    per-matrix arithmetic ``decode_flops_per_token`` prices, so the
    ladder's memory math can never drift from the FLOPs math."""
    D, V = cfg.d_model, cfg.vocab_size
    qkv = D * (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim
    wo = cfg.n_heads * cfg.head_dim * D
    if cfg.is_moe:
        mlp = D * cfg.n_experts + cfg.n_experts * 3 * D * cfg.expert_d_ff
    else:
        mlp = 3 * D * cfg.d_ff
    norms = 2 * D + (2 * cfg.head_dim if cfg.qk_norm else 0)
    per_layer = qkv + wo + mlp + norms
    head = 0 if cfg.tie_embeddings else D * V
    return cfg.n_layers * per_layer + V * D + D + head


def run_config_ladder(on_tpu: bool, measured: dict) -> list[dict]:
    """The bench config ladder: every serving rung the README claims,
    sized analytically (params, weight bytes, KV bytes/token, v5e-16GiB
    fit) so the ladder is DRY-RUN capable on any backend — the CPU
    smoke validates each config and its memory story every CI run, and
    real numbers ride the existing TPU evidence path (``BENCH_MODEL``
    selects the rung; the measured decode leg attaches here when its
    config matches).  The Qwen3-8B-int8 rung exists because VERDICT
    weak #3/#4 called the README's 8B-on-one-chip claim unmeasured:
    now the claim's arithmetic is asserted in-record every round, and
    the rung carries the measurement whenever BENCH_MODEL selects it."""
    import dataclasses as _dc

    from fusioninfer_tpu.benchmark.mfu import decode_flops_per_token
    from fusioninfer_tpu.models.config import get_preset

    v5e_hbm_gib = 16.0
    rungs = []
    for name, quant, kv_dtype in (
        ("qwen3-1.7b", "none", "bf16"),
        # the README's north-star serving config (8B on one 16 GiB
        # chip): int8 weights + int8 KV pages
        ("qwen3-8b", "int8", "int8"),
        ("qwen3-30b-a3b", "int8", "int8"),
    ):
        cfg = get_preset(name)
        if quant != "none":
            cfg = _dc.replace(cfg, quantization=quant)
        cfg = cfg.validate()  # the dry run: the config must construct
        params = model_param_count(cfg)
        w_bytes = params * (1 if quant == "int8" else 2)
        kv_per_tok = (2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim
                      * (1 if kv_dtype == "int8" else 2))
        ctx32k_gib = 32768 * kv_per_tok / 2**30
        weights_gib = w_bytes / 2**30
        rung = {
            "model": cfg.name,
            "quantization": quant,
            "kv_dtype": kv_dtype,
            "params_b": round(params / 1e9, 3),
            "weights_gib": round(weights_gib, 2),
            "kv_kib_per_token": round(kv_per_tok / 1024, 2),
            "kv_gib_per_32k_stream": round(ctx32k_gib, 2),
            # fit story: weights + one 32k stream + 2 GiB runtime
            # headroom (compiled programs, activations, host buffers)
            "fits_v5e_16gib": bool(
                weights_gib + ctx32k_gib + 2.0 <= v5e_hbm_gib),
            "flops_per_token_g_at_2k": round(
                decode_flops_per_token(cfg, 2048) / 1e9, 2),
            "dry_run": True,
        }
        m = measured.get((cfg.name, quant))
        if m is not None:
            rung["dry_run"] = False
            rung["measured"] = m
        rungs.append(rung)
    return rungs


def run_http(cfg, max_batch_size: int, cache_cfg, n_requests: int,
             concurrency: int, max_prompt: int, max_output: int,
             prefill_chunk: int | None = None,
             shared_prefix_len: int = 0,
             decode_burst_default: int = 8,
             load_top_k: int = 40) -> dict:
    from fusioninfer_tpu.benchmark.loadgen import run_http_load
    from fusioninfer_tpu.engine.engine import NativeEngine
    from fusioninfer_tpu.engine.server import EngineServer

    engine = NativeEngine(cfg, cache_cfg=cache_cfg, max_batch_size=max_batch_size,
                          prefill_chunk_size=prefill_chunk,
                          # token-budgeted scheduling: seeded by the chunk
                          # size (the shipped compat default) unless
                          # BENCH_TOKEN_BUDGET pins it for an A/B
                          token_budget=int(os.environ.get(
                              "BENCH_TOKEN_BUDGET", "0") or 0) or None,
                          # production default (cli.py --decode-burst): on a
                          # remote-attached chip the host round trip per
                          # decode step dominates serving throughput.
                          # 0 = off (classic stepping), like the CLI.
                          # The CPU smoke passes decode_burst_default=1 so
                          # the fused mixed-batch path (burst-1 engines)
                          # runs default-on there; BENCH_DECODE_BURST
                          # still pins either config for an A/B
                          decode_burst_steps=max(1, int(os.environ.get(
                              "BENCH_DECODE_BURST", "")
                              or decode_burst_default)),
                          # fused mixed-batch stepping (one weight pass
                          # for decode + prefill chunks); BENCH_FUSED_STEP=0
                          # restores the split dispatch for an A/B
                          fused_step=os.environ.get(
                              "BENCH_FUSED_STEP", "1") != "0",
                          # fused lm_head→top-k sampling (the serving
                          # default); BENCH_FUSED_SAMPLING=0 restores the
                          # unfused [rows, V] path for an A/B — streams
                          # are bit-identical, this is a perf switch
                          fused_sampling=os.environ.get(
                              "BENCH_FUSED_SAMPLING", "1") != "0")
    srv = EngineServer(
        model=cfg.name, host="127.0.0.1", port=0, engine=engine,
    )
    srv.start()
    try:
        # warm the jit signatures the measured load will hit, OUTSIDE
        # the measured window — a cold XLA compile mid-window poisons
        # the TTFT percentiles with a number that is not serving time.
        # That means every power-of-two prefill bucket up to max_prompt
        # (each is its own signature), at the LOAD's sampling mode
        # (loadgen sends temperature=0.8 with no top-k/top-p — the
        # "plain" static variant of sample/sample_first/decode_burst)
        # plus one greedy request for the "greedy" variants.
        import urllib.request as _ur

        def _warm(n_tokens: int, temperature: float) -> None:
            payload = {
                "model": cfg.name, "prompt": "w" * max(1, n_tokens - 2),
                "max_tokens": min(24, max_output),
                "temperature": temperature, "seed": 0,
            }
            if load_top_k > 0 and temperature > 0:
                # the measured load sends bounded top-k (the fused
                # lm_head→top-k serving shape): warm the "topk"
                # sampler/candidate variants, not "plain"
                payload["top_k"] = load_top_k
            body = json.dumps(payload).encode()
            req = _ur.Request(
                f"http://127.0.0.1:{srv.port}/v1/completions", body,
                headers={"Content-Type": "application/json"})
            _ur.urlopen(req, timeout=600).read()

        bucket = 32
        while True:
            _warm(bucket, 0.8)
            if bucket >= max_prompt:  # include the round-UP bucket for
                break                 # non-power-of-two max_prompt
            bucket *= 2
        _warm(32, 0.0)
        if shared_prefix_len:
            # the shared-prefix leg's cache hit is a suffix row of the
            # chunk forward: warm it with two requests sharing a prefix
            for tail in (" tail", " cont"):  # 2nd = cache hit → suffix
                body = json.dumps({
                    "model": cfg.name,
                    "prompt": "p" * shared_prefix_len + tail,
                    "max_tokens": min(24, max_output),
                    "temperature": 0.8, "seed": 0,
                }).encode()
                req = _ur.Request(
                    f"http://127.0.0.1:{srv.port}/v1/completions", body,
                    headers={"Content-Type": "application/json"})
                _ur.urlopen(req, timeout=600).read()
        engine.admission_timings.clear()
        result = run_http_load(
            f"http://127.0.0.1:{srv.port}",
            n_requests=n_requests, concurrency=concurrency, seed=0,
            max_prompt=max_prompt, max_output=max_output,
            shared_prefix_len=shared_prefix_len, top_k=load_top_k,
        )
        out = result.summary(n_chips=1)
        out["decode_burst"] = engine.burst_steps
        out["fused_step"] = engine.fused_step_enabled
        # fused-sampling evidence: the load above rode bounded top-k.
        # On burst-1 engines (the CPU smoke, the gated record) every
        # decode step sampled through the fused lm_head→top-k tail, so
        # ceiling_fraction (computed by the caller off this leg's
        # tok/s) is measured ON that path — the r15 re-measure of the
        # ROADMAP ceiling_fraction tail item.  Burst engines sample
        # in-scan inside decode_burst and never reach the fused tail:
        # `rides_burst` says so explicitly so a burst record's
        # enabled=true + steps=0 is never misread as fused evidence.
        out["fused_sampling"] = {
            "enabled": engine.fused_sampling_enabled,
            "steps": engine.fused_sampling_steps_total,
            "load_top_k": load_top_k,
            "rides_burst": engine.burst_steps > 1,
        }
        out["warmed"] = True  # compiles excluded from the window
        # token-budget scheduler evidence: budget, utilization, decision
        # counters and the adaptive-burst span histogram (engine/sched.py)
        out["scheduler"] = engine.sched.snapshot()
        # serving-path-gap evidence: weight-streaming forwards per step
        # (1.0 = every step is one weight pass, the fused-step target;
        # ≥ 2 is the split prefill+decode dispatch under mixed load)
        out["weight_passes_per_step"] = round(
            engine.sched.weight_passes_per_step(), 4)
        if shared_prefix_len:
            out["shared_prefix_len"] = shared_prefix_len
        # TTFT decomposition: server-side queue-wait (arrival → admission
        # pop) vs prefill compute (pop → first token) — says WHERE a fat
        # TTFT tail comes from (VERDICT r4 weak #2)
        timings = list(engine.admission_timings)
        if timings:
            qw = sorted(t[0] * 1000 for t in timings)
            pf = sorted(t[1] * 1000 for t in timings)

            def pct(xs, p):
                return round(xs[min(len(xs) - 1, int(p * len(xs)))], 1)

            out["queue_wait_ms"] = {"p50": pct(qw, 0.5), "p90": pct(qw, 0.9),
                                    "max": round(qw[-1], 1)}
            out["prefill_compute_ms"] = {"p50": pct(pf, 0.5),
                                         "p90": pct(pf, 0.9),
                                         "max": round(pf[-1], 1)}
        return out
    finally:
        srv.stop()


def run_sharedprefix(cfg, tp: int = 0) -> dict:
    """``workload_sharedprefix``: the shared-system-prompt + multi-turn
    leg that finally drives ``prefix_cache_hit_rate`` off 0.0 (every
    record through r05 reported 0.0 because the honest unique-prompt
    load deliberately avoids cache hits) and exercises the full KV
    hierarchy: a deliberately tight HBM pool forces warm system-prompt
    chains to offload to the host-DRAM tier and restore on later hits
    (docs/design/kv-hierarchy.md).

    Two passes of the same load shape: an UNRECORDED warmup pass (seed
    1) compiles every jit signature the measured traffic hits, then the
    measured pass (seed 2 — different system prompts, so its cold turns
    are truly cold while signatures stay warm).  Reports cold-vs-warm
    TTFT, the measured-pass hit rate, and the host tier's
    offload/restore/hit counter deltas.

    ``tp > 1`` drives the SAME workload through a tensor-parallel
    engine (mesh over the first ``tp`` devices, Megatron layout derived
    from the logical-axis rules) — the multi-chip leg that moves
    MULTICHIP evidence past the smoke-only dryrun (ROADMAP gap): the
    full prefix-cache + host-tier + residency machinery under a
    sharded KV cache."""
    from fusioninfer_tpu.benchmark.loadgen import run_sharedprefix_load
    from fusioninfer_tpu.engine.engine import NativeEngine
    from fusioninfer_tpu.engine.kv_cache import CacheConfig
    from fusioninfer_tpu.engine.kv_host_tier import HostKVTier
    from fusioninfer_tpu.engine.server import EngineServer

    mesh = None
    if tp > 1:
        import jax

        from fusioninfer_tpu.parallel import MeshConfig, build_mesh

        devices = jax.devices()
        if len(devices) < tp:
            raise RuntimeError(
                f"tp={tp} sharedprefix leg needs {tp} devices, "
                f"have {len(devices)}")
        mesh = build_mesh(MeshConfig(tp=tp), devices[:tp])

    # page_size 32 × 8 pages/seq = 256-token context; 32 usable pages
    # cannot retain 3 × 7-page system-prompt chains beside the ~6-20
    # pages 4 concurrent streams own — guaranteed reclaim churn, which
    # is the point: the host tier must carry the chains HBM cannot
    # retain, and the round-robin session interleave re-requests them
    cache_cfg = CacheConfig(n_pages=33, page_size=32, max_pages_per_seq=8)
    tier = HostKVTier(capacity_bytes=64 << 20)
    engine = NativeEngine(
        cfg, cache_cfg=cache_cfg, max_batch_size=4,
        token_budget=256, decode_burst_steps=1, fused_step=True,
        host_kv_tier=tier, mesh=mesh,
    )
    srv = EngineServer(model=cfg.name, host="127.0.0.1", port=0,
                       engine=engine)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        run_sharedprefix_load(base, seed=1)  # warmup: compile signatures
        tier.flush()
        before = tier.counters()
        sched_before = (engine.sched.kv_restores_total,
                        engine.sched.kv_restore_tokens_total,
                        engine.sched.kv_restore_deferred_total)
        engine.alloc.hit_tokens_total = 0
        engine.alloc.query_tokens_total = 0
        out = run_sharedprefix_load(base, seed=2)
        tier.flush()
        after = tier.counters()
        out["host_tier"] = {
            k: after[k] - before[k]
            for k in ("offloads", "restores", "host_hits",
                      "corrupt_dropped", "evictions")
        }
        out["host_tier"]["resident_blocks"] = after["resident_blocks"]
        # measured-pass deltas, same regime as host_tier above — the
        # warmup pass restores too and must not inflate the evidence
        out["scheduler_kv"] = {
            "kv_restores": engine.sched.kv_restores_total - sched_before[0],
            "kv_restore_tokens":
                engine.sched.kv_restore_tokens_total - sched_before[1],
            "kv_restore_deferred":
                engine.sched.kv_restore_deferred_total - sched_before[2],
        }
        out["warmed"] = True
        out["cache"] = {"n_pages": cache_cfg.n_pages,
                        "page_size": cache_cfg.page_size,
                        "host_tier_mb": 64}
        if tp > 1:
            out["tensor_parallel"] = tp
        return out
    finally:
        srv.stop()
        tier.close()


# CPU-virtual tp=2 sharedprefix leg, in a subprocess so the forced
# 2-device topology (and JAX_PLATFORMS=cpu on TPU rounds — libtpu is
# single-process and the bench holds the chip) never perturbs the main
# process's backend or calibration.  Protocol: TPSHAREDPREFIX {...}.
_TP_SHAREDPREFIX_SNIPPET = """
import dataclasses, json
import bench
from fusioninfer_tpu.models.config import get_preset

cfg = dataclasses.replace(get_preset("qwen3-tiny"), attn_impl="reference")
out = bench.run_sharedprefix(cfg, tp=2)
print("TPSHAREDPREFIX " + json.dumps(out), flush=True)
"""


def _run_snippet_leg(snippet: str, marker: str, env: dict,
                     timeout_s: float) -> dict:
    """Run one bench snippet subprocess; parse its marker JSON line."""
    proc = subprocess.run(
        [sys.executable, "-c", snippet], capture_output=True, text=True,
        timeout=timeout_s, cwd=_HERE, env=env,
    )
    for line in (proc.stdout or "").splitlines():
        if line.startswith(marker + " "):
            return json.loads(line[len(marker) + 1:])
    tail = (proc.stderr or "").strip().splitlines()[-4:]
    raise RuntimeError(
        f"{marker} subprocess rc={proc.returncode}: {' | '.join(tail)}")


def _error_keys(node, path: str = "") -> list[str]:
    """Paths of every ``error`` / ``*_error`` entry a leg left in the
    record."""
    found: list[str] = []
    if isinstance(node, dict):
        for key, val in node.items():
            here = f"{path}.{key}" if path else key
            if key == "error" or key.endswith("_error"):
                found.append(here)
            else:
                found += _error_keys(val, here)
    elif isinstance(node, list):
        for i, val in enumerate(node):
            found += _error_keys(val, f"{path}[{i}]")
    return found


def main() -> None:
    record: dict = {
        "metric": "decode_throughput",
        "value": 0.0,
        "unit": "tokens/sec/chip",
        "vs_baseline": 1.0,
        "backend": "unknown",
    }
    # forced BEFORE jax initializes a backend in-process; '' = whatever
    # jax finds, which must be an accelerator
    platform = os.environ.get("BENCH_PLATFORM", "")
    if platform:
        os.environ["JAX_PLATFORMS"] = platform
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    if not platform and jax.default_backend() == "cpu":
        # chip-or-fail: BENCH_PLATFORM=cpu is the only way onto the CPU
        # (an ambient JAX_PLATFORMS=cpu does not make a CPU run a bench)
        raise SystemExit(
            "bench.py found no accelerator (default backend is the CPU); "
            "BENCH_PLATFORM=cpu runs the explicit CPU smoke")
    try:
        from fusioninfer_tpu.benchmark.mfu import decode_mfu
        from fusioninfer_tpu.engine.kv_cache import CacheConfig
        from fusioninfer_tpu.models.config import get_preset

        from fusioninfer_tpu.ops.dispatch import is_tpu_backend

        backend = jax.default_backend()
        record["backend"] = backend
        record["device_kind"] = jax.devices()[0].device_kind
        record["device_count"] = len(jax.devices())
        on_tpu = is_tpu_backend()
        record["backend_is_tpu"] = on_tpu
        try:
            record["calibration_gflops"] = run_calibration(jax, on_tpu)
            record["calibration_version"] = _CALIBRATION_VERSION
        except Exception as e:  # auxiliary — never abort the bench
            record["calibration_error"] = f"{type(e).__name__}: {e}"
        if on_tpu:
            # Qwen3-1.7B shapes, 32-way continuous batch, 1 KiB-token
            # contexts: ~3.4 GiB weights + KV pages on a 16 GiB v5e chip.
            # BENCH_MODEL=qwen3-8b+int8 measures the BASELINE config-2 rung
            # (int8 weight-only, see models/quantization.py).
            base_cfg, batch = get_preset("qwen3-1.7b"), 32
            model_env = os.environ.get("BENCH_MODEL", "")
            if model_env:
                name, _, suffix = model_env.partition("+")
                base_cfg = get_preset(name)
                if suffix == "int8":
                    base_cfg = dataclasses.replace(base_cfg, quantization="int8")
            cache_cfg = CacheConfig(n_pages=32 * 8 + 1, page_size=128,
                                    max_pages_per_seq=8)
            prefix_len, warmup, steps = 128, 5, 64
            # longitudinal keys: the default config keeps its r2 literal
            # even when BENCH_MODEL names it explicitly (same measurement
            # must never fork series); other configs get sanitized names
            if base_cfg.name == "qwen3-1.7b" and base_cfg.quantization == "none":
                record["metric"] = "decode_throughput_qwen3_1.7b"
            else:
                safe = "".join(c if c.isalnum() else "_" for c in base_cfg.name)
                record["metric"] = f"decode_throughput_{safe}" + (
                    "_int8" if base_cfg.quantization == "int8" else ""
                )
        else:
            base_cfg, batch = get_preset("qwen3-tiny"), 8
            cache_cfg = CacheConfig(n_pages=33, page_size=64, max_pages_per_seq=4)
            prefix_len, warmup, steps = 32, 3, 64
            record["metric"] = "decode_throughput_tiny_cpu"

        decode: dict = {}
        # interpretability anchor for every kernel-vs-gather speedup in
        # this record: the portable gather baseline pays a
        # per-layer dynamic-slice of the stacked KV pool
        # (model_runner._cache_layer) before its cache[page_tables]
        # gather, while the Pallas kernels read the stacked pools in
        # place via their layer operand — cross-round speedup deltas
        # must be read against that baseline definition, not as pure
        # attention-kernel wins
        decode["gather_baseline_note"] = (
            "gather baseline includes a per-layer dynamic-slice of the "
            "stacked KV pool (model_runner._cache_layer); kernels read "
            "pages in place via their layer operand")
        tok_s = 0.0
        impl_used = None
        if on_tpu:
            # kernel path first; a kernel failure must still leave a number
            try:
                r = run_decode(jax, dataclasses.replace(base_cfg, attn_impl="flash"),
                               batch, cache_cfg, prefix_len, warmup, steps)
                decode["kernel_tok_s"] = round(r["tok_s"], 2)
                decode["kernel_dispersion"] = r
                tok_s, impl_used = r["tok_s"], "flash"
            except Exception as e:
                decode["kernel_error"] = f"{type(e).__name__}: {str(e)[:400]}"
            try:
                r = run_decode(jax, dataclasses.replace(base_cfg, attn_impl="reference"),
                               batch, cache_cfg, prefix_len, warmup, steps)
                decode["gather_tok_s"] = round(r["tok_s"], 2)
                decode["gather_dispersion"] = r
                if impl_used is None:
                    tok_s, impl_used = r["tok_s"], "reference"
            except Exception as e:
                decode["gather_error"] = f"{type(e).__name__}: {str(e)[:400]}"
            if "kernel_tok_s" in decode and "gather_tok_s" in decode and decode["gather_tok_s"]:
                decode["kernel_speedup"] = round(
                    decode["kernel_tok_s"] / decode["gather_tok_s"], 3
                )
            # int8 KV pages: half the attention HBM traffic per step
            try:
                r = run_decode(
                    jax, dataclasses.replace(base_cfg, attn_impl="flash"),
                    batch,
                    dataclasses.replace(cache_cfg, kv_dtype="int8"),
                    prefix_len, warmup, steps)
                decode["kernel_int8kv_tok_s"] = round(r["tok_s"], 2)
                if decode.get("kernel_tok_s"):
                    decode["int8kv_speedup"] = round(
                        r["tok_s"] / decode["kernel_tok_s"], 3)
            except Exception as e:
                decode["kernel_int8kv_error"] = (
                    f"{type(e).__name__}: {str(e)[:400]}")
            # fully-quantized serving config: int8 weights AND int8 KV
            # pages (models/quantization.py end to end) — the composed
            # speedup a quantized deployment actually gets.  Skipped
            # when BENCH_MODEL already pins int8 weights: the "composed"
            # datum would silently duplicate the int8-KV leg.
            if base_cfg.quantization != "int8":
                try:
                    r = run_decode(
                        jax,
                        dataclasses.replace(base_cfg, attn_impl="flash",
                                            quantization="int8"),
                        batch,
                        dataclasses.replace(cache_cfg, kv_dtype="int8"),
                        prefix_len, warmup, steps)
                    decode["kernel_int8w_int8kv_tok_s"] = round(
                        r["tok_s"], 2)
                    if decode.get("kernel_tok_s"):
                        decode["int8w_int8kv_speedup"] = round(
                            r["tok_s"] / decode["kernel_tok_s"], 3)
                except Exception as e:
                    decode["kernel_int8w_int8kv_error"] = (
                        f"{type(e).__name__}: {str(e)[:400]}")
            # in-place-cache probe (r5): decode at IDENTICAL context
            # depth over a small vs a 4× page pool.  ratio ≈ 1 → the
            # pools update in place; ratio ≫ 1 → some lowering still
            # copies the pool per step (the r5 bug class: the old
            # xs→ys scan threading + transposing scatter showed 3×
            # here).  This records the fix's hardware truth every
            # round without anyone re-deriving it.
            try:
                pool_sizes = {"small": 97, "large": 385}
                pool_t = {}
                for tag, npg in pool_sizes.items():
                    cc2 = CacheConfig(n_pages=npg, page_size=128,
                                      max_pages_per_seq=3)
                    r = run_decode(
                        jax, dataclasses.replace(base_cfg,
                                                 attn_impl="flash"),
                        batch, cc2, 128, 3, 32, reps=2)
                    pool_t[tag] = r["tok_s"]
                decode["pool_scaling"] = {
                    "small_pages": pool_sizes["small"],
                    "large_pages": pool_sizes["large"],
                    "small_tok_s": round(pool_t["small"], 2),
                    "large_tok_s": round(pool_t["large"], 2),
                    "ratio": round(pool_t["small"] / pool_t["large"], 3),
                }
            except Exception as e:
                decode["pool_scaling_error"] = (
                    f"{type(e).__name__}: {str(e)[:400]}")
            # long-context ragged leg: stratified 256..2048-token contexts
            # (the continuous-batching steady state).  The bench's base
            # shape (uniform ~200-token contexts, 8-page tables) hides
            # the paged kernel's point — there, attention is a sliver of
            # step time and kernel ≈ gather (r5 first record: 0.997).
            # With 16-page tables and ragged depths the gather path
            # materializes 2048 tokens/row for every row while the
            # kernel streams only live pages.
            lc_steps, lc_ps, lc_mp = 64, 128, 16
            tail = decode_tokens_needed(0, warmup, lc_steps)
            lens = stratified_lens(batch, lc_ps * lc_mp, tail)
            # pool sized to actual need (not batch×16 pages): a fully
            # provisioned 16-page × 32-row pool is ~7.5 GiB of KV at
            # this model's [KV=8, Hd=128] × 28 layers
            long_cache = CacheConfig(
                n_pages=decode_pool_pages(lens, warmup, lc_steps, lc_ps),
                page_size=lc_ps, max_pages_per_seq=lc_mp)
            # one try per impl: a kernel failure must still leave the
            # gather baseline (same isolation as the base legs)
            for impl, key in (("flash", "longctx_kernel"),
                              ("reference", "longctx_gather")):
                try:
                    r = run_decode(
                        jax, dataclasses.replace(base_cfg, attn_impl=impl),
                        batch, long_cache, 0, warmup, lc_steps,
                        prefix_lens=lens)
                    decode[f"{key}_tok_s"] = round(r["tok_s"], 2)
                    decode[f"{key}_dispersion"] = r
                except Exception as e:
                    decode[f"{key}_error"] = (
                        f"{type(e).__name__}: {str(e)[:400]}")
            if decode.get("longctx_gather_tok_s") and \
                    decode.get("longctx_kernel_tok_s"):
                decode["longctx_kernel_speedup"] = round(
                    decode["longctx_kernel_tok_s"]
                    / decode["longctx_gather_tok_s"], 3)
        else:
            from fusioninfer_tpu.ops import dispatch

            r = run_decode(jax, base_cfg, batch, cache_cfg,
                           prefix_len, warmup, steps)
            tok_s = r["tok_s"]
            decode["dispersion"] = r
            impl_used = dispatch.resolve_attn(base_cfg.attn_impl)
        decode["attn_impl_used"] = impl_used
        record["decode"] = decode
        record["value"] = round(tok_s, 2)

        disp = decode.get("dispersion") or decode.get("kernel_dispersion") \
            or decode.get("gather_dispersion")
        if disp:
            # the headline value is the MEDIAN of n_reps windows; rel_iqr
            # is the noise floor a vs_prev delta must clear to mean
            # anything (the r4 record's single window could not)
            record["dispersion"] = {k: disp[k] for k in
                                    ("reps", "iqr", "rel_iqr", "steps",
                                     "n_reps")}
        try:
            record["admissions"] = run_admissions(
                dataclasses.replace(base_cfg, attn_impl=impl_used or "auto"),
                cache_cfg, max_batch_size=8 if not on_tpu else 16,
                n_requests=24 if not on_tpu else 64)
        except Exception as e:
            record["admissions"] = {
                "error": f"{type(e).__name__}: {str(e)[:200]}"}

        # raw-kernel microbench: the ragged kernel's own evidence leg
        # (ragged-vs-gather, mfu_box with dispersion) — independent of
        # the full-model decode legs above
        try:
            record["kernel_microbench"] = run_kernel_microbench(
                jax, on_tpu, record.get("calibration_gflops"))
        except Exception as e:
            record["kernel_microbench"] = {
                "error": f"{type(e).__name__}: {str(e)[:400]}"}

        # the serving config ladder (incl. the README's Qwen3-8B-int8
        # rung): dry-run memory/FLOPs arithmetic on every backend, the
        # measured decode leg attached when BENCH_MODEL ran that rung
        try:
            measured = {}
            if on_tpu and tok_s:
                measured[(base_cfg.name, base_cfg.quantization)] = {
                    "tok_s_per_chip": round(tok_s, 2),
                    "metric": record["metric"],
                }
            record["config_ladder"] = run_config_ladder(on_tpu, measured)
        except Exception as e:
            record["config_ladder"] = {
                "error": f"{type(e).__name__}: {str(e)[:400]}"}

        # MFU context: mean position over the FULL timed span (reps
        # windows), else attention FLOPs are understated
        avg_ctx = prefix_len + warmup + (steps * _DECODE_REPS) // 2
        mfu = decode_mfu(base_cfg, tok_s, avg_ctx, jax.devices()[0].device_kind)
        if mfu is not None:
            record["mfu"] = round(mfu, 4)
        if tok_s and record.get("calibration_gflops"):
            # also report FLOP/s against what THIS box measurably
            # sustains on a dense matmul chain (run_calibration)
            from fusioninfer_tpu.benchmark.mfu import decode_flops_per_token

            record["mfu_box"] = round(
                tok_s * decode_flops_per_token(base_cfg, avg_ctx)
                / (record["calibration_gflops"] * 1e9), 4)

        if os.environ.get("BENCH_SKIP_HTTP", "") != "1" and impl_used is not None:
            # serve with whichever attention impl the decode phase proved out
            http_cfg = dataclasses.replace(base_cfg, attn_impl=impl_used)
            if on_tpu:
                # serving config sized to the chip: batch 32 (the raw
                # decode leg's batch) with closed-loop concurrency 32 so
                # the continuous batch can actually fill, pool ~4.7 GiB
                # beside ~3.4 GiB of weights on a 16 GiB v5e — round 5's
                # decode burst + pipelining make the serving loop
                # chip-bound enough to feed it
                http_cache = CacheConfig(n_pages=32 * 10 + 1, page_size=128,
                                         max_pages_per_seq=10)
                # chunked prefill is the shipped serving config: a long
                # prompt must not stall every stream's inter-token latency
                chunk = 512
                record["http"] = run_http(
                    http_cfg, max_batch_size=32, cache_cfg=http_cache,
                    n_requests=64, concurrency=32,
                    max_prompt=1024, max_output=128,
                    prefill_chunk=chunk,
                )
                record["http"]["prefill_chunk"] = chunk
            else:
                # the CPU smoke must run the SHIPPED serving config:
                # chunked prefill on, so regressions in the chunked path
                # are visible every CI run (VERDICT r3 weak #4)
                http_cache = CacheConfig(n_pages=8 * 4 + 1, page_size=64,
                                         max_pages_per_seq=4)
                chunk = 64
                # burst 1 on CPU: burst-1 engines run the fused
                # mixed-batch step default-on — the smoke then gates
                # weight_passes_per_step ≈ 1 under mixed load
                record["http"] = run_http(
                    http_cfg, max_batch_size=8, cache_cfg=http_cache,
                    n_requests=12, concurrency=4,
                    max_prompt=128, max_output=32,
                    prefill_chunk=chunk, decode_burst_default=1,
                )
                record["http"]["prefill_chunk"] = chunk
                # prefix-cache-hit mix: shared 96-token prefix across
                # requests exercises the cache-hit × chunked-prefill path
                record["http_prefix_mix"] = run_http(
                    http_cfg, max_batch_size=8, cache_cfg=http_cache,
                    n_requests=8, concurrency=4,
                    max_prompt=128, max_output=32,
                    prefill_chunk=chunk, shared_prefix_len=96,
                    decode_burst_default=1,
                )
            # decode-ceiling fraction: HTTP output tok/s/chip over the
            # SAME-config raw decode tok/s — the serving-path-gap metric
            # (VERDICT r5 ask #1: 126/550 = 0.23 was the round-5 truth)
            for leg in ("http", "http_prefix_mix"):
                if leg in record and tok_s:
                    record[leg]["ceiling_fraction"] = round(
                        record[leg].get("output_tok_per_s_per_chip", 0.0)
                        / tok_s, 4)
            # hierarchical-KV workload leg (shared system prompts +
            # multi-turn): hit rate, warm-vs-cold TTFT, host-tier
            # offload/restore evidence — gated by check_bench_record
            try:
                record["workload_sharedprefix"] = run_sharedprefix(
                    http_cfg)
            except Exception as e:
                record["workload_sharedprefix"] = {
                    "error": f"{type(e).__name__}: {str(e)[:400]}"}
            # the SAME workload through a tp=2 tensor-parallel engine
            # (subprocess, 2 virtual CPU devices): prefix cache + host
            # tier + residency under a sharded KV cache — MULTICHIP
            # evidence past the smoke-only dryrun (ROADMAP gap)
            try:
                tp_env = dict(os.environ)
                tp_env["JAX_PLATFORMS"] = "cpu"
                flags = tp_env.get("XLA_FLAGS", "")
                if "xla_force_host_platform_device_count" not in flags:
                    tp_env["XLA_FLAGS"] = (
                        flags + " --xla_force_host_platform_device_count=2"
                    ).strip()
                record["workload_sharedprefix_tp"] = _run_snippet_leg(
                    _TP_SHAREDPREFIX_SNIPPET, "TPSHAREDPREFIX", tp_env,
                    1200)
                record["workload_sharedprefix_tp"]["backend"] = (
                    "cpu-virtual")
            except Exception as e:
                record["workload_sharedprefix_tp"] = {
                    "error": f"{type(e).__name__}: {str(e)[:400]}"}
    except Exception as e:  # never a traceback instead of the JSON line
        record["error"] = f"{type(e).__name__}: {e}"
    line = json.dumps(record)
    # sidecar copy: the driver captures a bounded log tail, which truncated
    # the round-2 record — the file is the canonical evidence
    try:
        sidecar = pathlib.Path(__file__).resolve().parent / "BENCH_OUT.json"
        sidecar.write_text(line + "\n")
    except OSError as e:
        print(f"sidecar write failed: {e}", file=sys.stderr, flush=True)
    print(line)
    failed = _error_keys(record)
    if failed and not platform:
        # the record above is the evidence; the exit code is the verdict
        print(f"bench legs failed: {', '.join(failed)}", file=sys.stderr,
              flush=True)
        sys.exit(1)


def fleet_smoke(argv: list[str]) -> int:
    """``python bench.py --fleet-smoke [--out FLEET_OUT.json]``: the
    closed-loop fleet harness (fusioninfer_tpu.fleetsim) as a bench
    entry point — real manager + engines + EPP + autoscaler under
    faulted load, evidence gated by tools/check_fleet_record.py."""
    from fusioninfer_tpu.fleetsim.__main__ import main as fleet_main

    return fleet_main([a for a in argv if a != "--fleet-smoke"])


if __name__ == "__main__":
    if "--fleet-smoke" in sys.argv[1:]:
        sys.exit(fleet_smoke(sys.argv[1:]))
    main()
