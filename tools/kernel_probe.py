"""The ragged paged-attention kernel alone, on the chip, at the serving
cells' shape: what a call, a row and a page cost, and what a program
that carries the kernel pays at its first dispatch (ISSUE 31).

    chiprun -- python tools/kernel_probe.py
    chiprun -- python tools/kernel_probe.py \
        --variant parent=.archive_parent/fusioninfer_tpu/ops/paged_attention.py \
        --variant ring2=fusioninfer_tpu/ops/paged_attention.py@RAGGED_RING_SLOTS=2
    JAX_PLATFORMS=cpu python tools/kernel_probe.py --tiny   # rehearsal: control flow only

Shape: 8 KV heads x group 2 x 128, page 128, a stacked pool of 744 pages
x 2 layers (bfloat16), 32 rows x 32-page tables, ``kv_splits`` 8
(``--kv-splits 0``: the single-walk grid), ``block_q`` 8: what
``qwen3-1.7b``'s cells trace.  Cases:

* ``decode_r<n>_<ctx>``: n in {1, 2, 8, 16} decode rows at contexts of
  0.8 k and 3.8 k tokens (7 and 30 pages a row);
* ``chunk24_at800``, ``chunk448_at0``, ``chunk448_at1024``: one chunk
  row (beside no decode rows).

A timing is the median over 5 repeats of ONE jitted loop of 50 kernel
calls (the layer alternates, each call's query is the output of the one
before it, the rows' walk lists are built once outside the loop as a
forward builds them outside its layer scan, ``block_until_ready``
fences the loop) divided by 50.  The
fit ``us = c + a * rows + b * pages`` over the decode cases says what a
row's boundaries cost (``a``) and what a page costs (``b``, against
``page_us_at_hbm_peak``).  ``cold_waits``: page copies a call waits for
with nothing else in flight — one per column that has a walk with the
page stream, one per walk without it (``walks``); both counted from the
tree's own walk lists.

``first_dispatch``: in a process of its own that finds the program in
the persistent compile cache (a process before it put it there), the
seconds of trace, lower, compile (= retrieve + load) and first run of
the kernel alone at the decode (T 16) and the chunk (T 512) shape.

``--latent`` probes ``ops/mla_attention.py``'s kernel instead, at
``deepseek-v2-ep4``'s cell shapes (ISSUE 33, Step 0): a pool of
``[5, 1, 2048, 128, 640]`` bfloat16, 128 heads, rank 512 + rope 64, 64
rows x 64-page tables.  Cases: ``decode_r64_3800`` and ``decode_r1_3800``
(decode rows at 3.8 k), ``fill_r64x13_at1700`` (the fill's step: 64 chunk
rows of 13 tokens at 1.7 k), ``chunk832_at0`` and ``chunk832_at3000``.
Beside µs a call: the (tile, row, page) visits of the call and µs a
visit, against what a visit's bytes take at the HBM peak and its dots
(live query rows x page x 2 x (2 rank + rope)) at the MXU peak;
``roofline_pct`` is the larger of the two over the measured time.
``--ring 2 4`` adds the tree's kernel at those ring depths
(``@MLA_RING_SLOTS=n``; a slot is ``MLA_PAGES_PER_UPDATE`` pages).

    chiprun -- python tools/kernel_probe.py --latent --ring 2 4 \
        --variant parent=.archive_parent/fusioninfer_tpu/ops/mla_attention.py \
        --variant unit1=fusioninfer_tpu/ops/mla_attention.py@MLA_PAGES_PER_UPDATE=1

``--latent longcat-flash-ep32`` probes the same kernel at that preset's
shapes (ISSUE 34): 64 heads, a pool of ``[8, 1, 2048, 128, 640]``, 64 rows
x 32-page tables; cases ``decode_r64_1100``, ``decode_r1_1100``,
``fill_r64x12_at400`` and ``chunk768_at0`` / ``chunk768_at1500``.

``--gmm`` times the routed experts' grouped product (megablox ``gmm``
through a whole stack of ``4 x 16`` experts read in place, as
``transformer.grouped_matmul`` hands it over) at ``longcat-flash-ep32``'s
two matrices, 6144 x 2048 and 2048 x 6144, for a decode pass (64 tokens x
12 assignments, 16 of them local over ~10 experts) and a chunk pass (832
tokens, ~208 local rows), one line a tiling: ``transformer.gmm_tiling``'s
own first, then the candidates of ``GMM_CANDIDATES``.

``--gmm smallthinker-21b-a3b`` times it at that preset's two matrices
(2560 x 768 and back, a stack of ``8 x 64`` experts, every assignment
local: 32 tokens x 6 and 1024 tokens x 6).  ``--by-kind`` times the
ragged kernel at ``smallthinker-21b-a3b``'s shapes (4 KV heads x 7, a
table of 128 pages) as its two layer kinds call it, full attention and
a window of 4096, each on the single-walk grid and on 8 splits: 32
decode rows at 1 k / 8 k / 12 k and a 1 024-token chunk at 8 k
(``pick_kv_splits``'s rule by kind is read off these lines).

``--variant name=path[@CONST=int[,CONST=int]]`` loads ANOTHER copy of
``paged_attention.py`` (``mla_attention.py`` with ``--latent``) under
its own name, optionally with one module
constant set before anything is traced, so one call compares kernels on
one chip.  Every process but the first is a child (``--child``): the
parent never touches jax, because a chip belongs to one process at a
time.  The last line printed is one JSON object; the same goes to
``--out``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "perfbench")]

TREE = "fusioninfer_tpu/ops/paged_attention.py"
CALLS, REPEATS = 50, 5
REAL = dict(KV=8, G=2, Hd=128, page=128, n_pages=744, layers=2, table=32,
            rows=32, contexts=(800, 3800), decode_rows=(1, 2, 8, 16),
            chunks=((24, 800), (448, 0), (448, 1024)), kv_splits=8)
TINY = dict(KV=2, G=2, Hd=64, page=16, n_pages=40, layers=2, table=8,
            rows=8, contexts=(40, 100), decode_rows=(1, 4),
            chunks=((12, 30),), kv_splits=8)
LATENT_TREE = "fusioninfer_tpu/ops/mla_attention.py"
LATENT_REAL = dict(H=128, rank=512, rope=64, W=640, page=128, n_pages=2048,
                   layers=5, table=64, rows=64, first_T=(64, 1024),
                   cases={"decode_r64_3800": ([1] * 64, [3799] * 64),
                          "decode_r1_3800": ([1], [3799]),
                          "fill_r64x13_at1700": ([13] * 64, [1700] * 64),
                          "chunk832_at0": ([832], [0]),
                          "chunk832_at3000": ([832], [3000])})
LATENT_LONGCAT = dict(H=64, rank=512, rope=64, W=640, page=128, n_pages=2048,
                      layers=8, table=32, rows=64, first_T=(64, 1024),
                      cases={"decode_r64_1100": ([1] * 64, [1099] * 64),
                             "decode_r1_1100": ([1], [1099]),
                             "fill_r64x12_at400": ([12] * 64, [400] * 64),
                             "chunk768_at0": ([768], [0]),
                             "chunk768_at1500": ([768], [1500])})
LATENT_SHAPES = {"deepseek-v2-ep4": LATENT_REAL,
                 "longcat-flash-ep32": LATENT_LONGCAT}
# the grouped product at longcat-flash-ep32's shapes: (tokens, local rows)
GMM_REAL = dict(layers=4, held=16, D=6144, F=2048, k=12,
                passes={"decode_t64": (64, 16), "chunk_t832": (832, 208)})
GMM_TINY = dict(layers=2, held=4, D=256, F=128, k=4,
                passes={"decode_t8": (8, 6), "chunk_t48": (48, 30)})
# smallthinker-21b-a3b holds every expert: all assignments are local
GMM_SMALLTHINKER = dict(layers=8, held=64, D=2560, F=768, k=6,
                        passes={"decode_t32": (32, 192),
                                "chunk_t1024": (1024, 6144)})
GMM_SHAPES = {"longcat-flash-ep32": GMM_REAL,
              "smallthinker-21b-a3b": GMM_SMALLTHINKER}
# the ragged kernel as smallthinker-21b-a3b's two layer kinds call it
KIND_REAL = dict(KV=4, G=7, Hd=128, page=128, n_pages=4200, layers=2,
                 table=128, rows=64, windows=(None, 4096), splits=(0, 8),
                 cases={"decode_r32_1k": ([1] * 32, [1023] * 32),
                        "decode_r32_8k": ([1] * 32, [8191] * 32),
                        "decode_r32_12k": ([1] * 32, [12287] * 32),
                        "chunk1024_at8k": ([1024], [8192])})
KIND_TINY = dict(KV=2, G=3, Hd=64, page=16, n_pages=80, layers=2, table=16,
                 rows=8, windows=(None, 64), splits=(0, 8),
                 cases={"decode_r4_200": ([1] * 4, [199] * 4),
                        "chunk32_at100": ([32], [100])})
GMM_CANDIDATES = {
    (2560, 768): [(128, 1280, 768), (128, 2560, 384), (256, 2560, 768),
                  (128, 2560, 256), (512, 2560, 768)],
    (768, 2560): [(128, 768, 1280), (128, 768, 640), (256, 768, 2560),
                  (128, 384, 2560), (512, 768, 2560)],
    (6144, 2048): [(128, 2560, 768), (128, 2048, 512), (128, 3072, 512),
                   (128, 1536, 1024), (128, 3072, 1024), (128, 6144, 512)],
    (2048, 6144): [(128, 2560, 768), (128, 2048, 768), (128, 2048, 1536),
                   (128, 1024, 1536), (128, 1024, 2048), (128, 2048, 512)],
}
LATENT_TINY = dict(H=4, rank=64, rope=16, W=128, page=16, n_pages=80,
                   layers=2, table=8, rows=8, first_T=(16, 32),
                   cases={"decode_r8_100": ([1] * 8, [99] * 8),
                          "decode_r1_100": ([1], [99]),
                          "fill_r8x3_at50": ([3] * 8, [50] * 8),
                          "chunk24_at0": ([24], [0]),
                          "chunk24_at60": ([24], [60])})


def _load(name: str, spec: str):
    path, _, setting = spec.partition("@")
    s = importlib.util.spec_from_file_location(
        f"_probe_pa_{name}", os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(s)
    sys.modules[s.name] = mod
    s.loader.exec_module(mod)
    for one in filter(None, setting.split(",")):
        attr, value = one.split("=")
        if not hasattr(mod, attr):
            raise SystemExit(f"{path} has no constant {attr}")
        setattr(mod, attr, int(value))
    return mod


def _case(shape: dict, q_lens: list[int], starts: list[int]):
    """Host descriptors of one call: the given rows first, inert rows
    up to ``shape['rows']``, every live row on pages of its own."""
    import numpy as np

    R, mp, ps = shape["rows"], shape["table"], shape["page"]
    ql = np.zeros(R, np.int32)
    st = np.zeros(R, np.int32)
    ql[:len(q_lens)], st[:len(starts)] = q_lens, starts
    qb = np.concatenate([[0], np.cumsum(ql)[:-1]]).astype(np.int32)
    tables = np.zeros((R, mp), np.int32)
    nxt = 0
    for r in range(len(q_lens)):
        need = -(-int(st[r] + ql[r]) // ps)
        tables[r, :need] = (nxt + np.arange(need)) % (shape["n_pages"] - 1)
        nxt += need
    T = max(16, 1 << (int(ql.sum()) - 1).bit_length())  # the pow2 bucket
    return T, tables, st, qb, ql


def _cases(shape: dict) -> dict:
    out = {}
    for ctx in shape["contexts"]:
        for n in shape["decode_rows"]:
            out[f"decode_r{n}_{ctx}"] = _case(shape, [1] * n, [ctx - 1] * n)
    for n, at in shape["chunks"]:
        out[f"chunk{n}_at{at}"] = _case(shape, [n], [at])
    return out


def _walk_counts(tree, shape: dict, T: int, st, qb, ql) -> dict:
    """Pages fetched, walks and columns-with-a-walk of one call, from the
    tree's walk lists (a chunk row's every tile walks its causal span)."""
    import jax.numpy as jnp
    import numpy as np

    S = shape["kv_splits"]
    nb = T // tree.RAGGED_BLOCK_Q
    split = dict(n_cols=S, cpp=tree.KV_SPLIT_CHUNKS // S, chunk_pages=-(
        -shape["table"] // tree.KV_SPLIT_CHUNKS)) if S else {}
    tile_walks, _, first, end = (np.asarray(a) for a in tree._ragged_walks(
        jnp.asarray(qb), jnp.asarray(ql), jnp.asarray(st), nb=nb,
        block_q=tree.RAGGED_BLOCK_Q, page_size=shape["page"], window=None,
        **split))
    per_col = tile_walks.reshape(max(S, 1), nb + 1)[:, -1]
    return {"pages": int((end - first).sum()), "walks": int(per_col.sum()),
            "columns": int((per_col > 0).sum())}


def _operands(shape: dict, T: int):
    import jax
    import jax.numpy as jnp

    KV, G, Hd = shape["KV"], shape["G"], shape["Hd"]
    pool = (shape["layers"], KV, shape["n_pages"], shape["page"], Hd)
    ks = jax.random.split(jax.random.key(0), 3)
    return (jax.random.normal(ks[0], (T, KV * G, Hd), jnp.bfloat16),
            jax.random.normal(ks[1], pool, jnp.bfloat16),
            jax.random.normal(ks[2], pool, jnp.bfloat16))


def _kernel(mod, shape: dict, interpret: bool):
    def call(q, kp, vp, tables, st, qb, ql, layer, **kw):
        if not shape["kv_splits"]:  # the single-walk grid (ROADMAP S2's A/B)
            return mod.ragged_paged_attention(
                q, kp, vp, tables, st, qb, ql, coalesce=True,
                interpret=interpret, layer=layer, **kw)
        return mod.ragged_paged_attention_kvsplit(
            q, kp, vp, tables, st, qb, ql, kv_splits=shape["kv_splits"],
            interpret=interpret, layer=layer, **kw)
    return call


def _median_us(many, args) -> float:
    """Median µs a call over ``REPEATS`` runs of the jitted loop ``many``
    (``CALLS`` kernel calls), after one run that compiles it."""
    many(*args).block_until_ready()
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        many(*args).block_until_ready()
        times.append((time.perf_counter() - t0) / CALLS * 1e6)
    return statistics.median(times)


def _first_dispatch(call, args) -> dict:
    """Seconds of trace, lower, compile (= retrieve + load from a warm
    cache) and first run of ``call``, and its lowered module's size."""
    import jax

    jax.block_until_ready(args)
    t = [time.perf_counter()]
    traced = jax.jit(call).trace(*args)
    t.append(time.perf_counter())
    lowered = traced.lower()
    t.append(time.perf_counter())
    compiled = lowered.compile()
    t.append(time.perf_counter())
    compiled(*args).block_until_ready()
    t.append(time.perf_counter())
    return dict(zip(("trace_s", "lower_s", "compile_s", "run_s"),
                    (b - a for a, b in zip(t, t[1:]))),
                module_chars=len(lowered.as_text()))


def child_time(mod, shape: dict, interpret: bool) -> dict:
    """µs a call of every case, the fit, and the error against the jnp
    oracle on the mixed case."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tree = _load("tree_lists", TREE)
    call = _kernel(mod, shape, interpret)
    L = shape["layers"]

    @jax.jit
    def many(q, kp, vp, tables, st, qb, ql):
        kw = {}
        if hasattr(mod, "ragged_walk_lists"):
            # as the engine does it: the rows' walk lists are built once
            # a forward, outside the loop over layers
            kw["walks"] = mod.ragged_walk_lists(
                q, kp, vp, tables, st, qb, ql, kv_splits=shape["kv_splits"])

        def body(i, q):
            return call(q, kp, vp, tables, st, qb, ql,
                        jnp.int32(i % L), **kw).reshape(q.shape)
        return jax.lax.fori_loop(0, CALLS, body, q)

    import peaks  # perfbench/peaks.py: the one table of chip peaks

    peak = ({"hbm_bytes_per_s": 819e9} if interpret  # a rehearsal's stand-in
            else peaks.peaks_for(jax.devices()[0].device_kind))
    page_bytes = 2 * shape["KV"] * shape["page"] * shape["Hd"] * 2
    page_us = page_bytes / peak["hbm_bytes_per_s"] * 1e6
    out, fit_rows = {}, []
    for name, (T, tables, st, qb, ql) in _cases(shape).items():
        q, kp, vp = _operands(shape, T)
        args = (q, kp, vp, *map(jnp.asarray, (tables, st, qb, ql)))
        us = _median_us(many, args)
        counts = _walk_counts(tree, shape, T, st, qb, ql)
        pages = counts.pop("pages")
        out[name] = {"us_per_call": us, "pages_read": pages,
                     "roofline_pct": 100 * pages * page_us / us,
                     "cold_waits": counts}
        if name.startswith("decode"):
            fit_rows.append((int((ql > 0).sum()), pages, us))
        print(f"  {name}: {us:.1f} us, {pages} pages, "
              f"{out[name]['roofline_pct']:.1f} % of the memory roofline",
              flush=True)
    A = np.array([[1.0, r, p] for r, p, _ in fit_rows])
    c, a, b = np.linalg.lstsq(A, np.array([u for *_, u in fit_rows]),
                              rcond=None)[0]
    out["fit"] = {"c_us_per_call": c, "a_us_per_row": a, "b_us_per_page": b,
                  "page_us_at_hbm_peak": page_us}
    # the oracle, on a call that mixes decode rows and a chunk row
    ctx = shape["contexts"][0]
    n, at = shape["chunks"][0]
    T, tables, st, qb, ql = _case(shape, [1, 1, n, 1], [ctx, 5, at, ctx // 2])
    q, kp, vp = _operands(shape, T)
    d = tuple(map(jnp.asarray, (tables, st, qb, ql)))
    got = jax.jit(call)(q, kp, vp, *d, jnp.int32(1))
    want = tree.reference_ragged_paged_attention(q, kp[1], vp[1], *d)
    live = np.asarray(tree.ragged_token_rows(d[2], d[3], T)[2])
    out["max_abs_err_vs_oracle"] = float(np.abs(
        np.asarray(got, np.float32) - np.asarray(want, np.float32))[live].max())
    return out


def child_first(mod, shape: dict, interpret: bool) -> dict:
    """Seconds of each stage of a first dispatch of the kernel alone."""
    import jax.numpy as jnp

    from fusioninfer_tpu.engine import aot

    aot.configure_cache(min_compile_seconds=0.0)
    call = _kernel(mod, shape, interpret)
    out = {}
    for T in (16, 32 if shape["page"] < 128 else 512):
        _, tables, st, qb, ql = _case(shape, [1], [shape["contexts"][0]])
        q, kp, vp = _operands(shape, T)
        args = (q, kp, vp, *map(jnp.asarray, (tables, st, qb, ql)),
                jnp.int32(0))
        out[f"t{T}"] = _first_dispatch(call, args)
    return out


# -- the latent (MLA) leg ------------------------------------------------

def _latent_operands(shape: dict, T: int):
    import jax
    import jax.numpy as jnp

    pool = (shape["layers"], 1, shape["n_pages"], shape["page"], shape["W"])
    ks = jax.random.split(jax.random.key(0), 3)
    return (jax.random.normal(ks[0], (T, shape["H"], shape["rank"]),
                              jnp.bfloat16) * 0.05,
            jax.random.normal(ks[1], (T, shape["H"], shape["rope"]),
                              jnp.bfloat16) * 0.05,
            jax.random.normal(ks[2], pool, jnp.bfloat16))


def _latent_visits(mod, shape: dict, T: int, st, qb, ql) -> dict:
    """(tile, row, page) visits of one call and the query rows they
    score, from the tree's walk lists."""
    import jax.numpy as jnp
    import numpy as np

    from fusioninfer_tpu.ops import paged_attention as pa

    bq = mod.MLA_BLOCK_Q
    tile_walks, w_row, first, end = (np.asarray(a) for a in pa._ragged_walks(
        jnp.asarray(qb), jnp.asarray(ql), jnp.asarray(st), nb=T // bq,
        block_q=bq, page_size=shape["page"], window=None))
    visits = rows = 0
    for t in range(T // bq):
        for w in range(tile_walks[t], tile_walks[t + 1]):
            r = w_row[w]
            live = min(qb[r] + ql[r], (t + 1) * bq) - max(qb[r], t * bq)
            visits += int(end[w] - first[w])
            rows += int(end[w] - first[w]) * int(live) * shape["H"]
    return {"visits": visits, "walks": int(tile_walks[-1]), "q_rows": rows}


def latent_child_time(mod, shape: dict, interpret: bool) -> dict:
    """µs a call of every latent case, µs a page visit against its bytes
    and its dots, and the error against the jnp oracle on a mixed call."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import peaks  # perfbench/peaks.py: the one table of chip peaks

    L, rank = shape["layers"], shape["rank"]
    kw = dict(rank=rank, interpret=interpret)

    @jax.jit
    def many(ql_, qr, pages, tables, st, qb, ql):
        walks = {}
        if hasattr(mod, "mla_walk_lists"):  # once a forward, as the engine
            walks["walks"] = mod.mla_walk_lists(ql_.shape[0], pages, st, qb, ql)

        def body(i, q):
            return mod.mla_ragged_paged_attention(
                q, qr, pages, tables, st, qb, ql, layer=jnp.int32(i % L),
                **kw, **walks)
        return jax.lax.fori_loop(0, CALLS, body, ql_)

    peak = ({"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}  # a stand-in
            if interpret else peaks.peaks_for(jax.devices()[0].device_kind))
    page_us = shape["page"] * shape["W"] * 2 / peak["hbm_bytes_per_s"] * 1e6
    row_us = (shape["page"] * 2 * (2 * rank + shape["rope"])
              / peak["flops_bf16"] * 1e6)
    out = {}
    for name, (q_lens, starts) in shape["cases"].items():
        T, tables, st, qb, ql = _case(shape, q_lens, starts)
        q_lat, q_rope, pages = _latent_operands(shape, T)
        args = (q_lat, q_rope, pages, *map(jnp.asarray, (tables, st, qb, ql)))
        us = _median_us(many, args)
        c = _latent_visits(mod, shape, T, st, qb, ql)
        least = max(c["visits"] * page_us, c["q_rows"] * row_us)
        out[name] = {"us_per_call": us, **c, "us_per_visit": us / c["visits"],
                     "visit_us_at_hbm_peak": page_us,
                     "visit_us_at_mxu_peak": c["q_rows"] * row_us / c["visits"],
                     "roofline_pct": 100 * least / us}
        print(f"  {name}: {us:.1f} us, {c['visits']} page visits, "
              f"{us / c['visits']:.3f} us a visit, "
              f"{out[name]['roofline_pct']:.1f} % of its roofline", flush=True)
    # the oracle, on a call that mixes decode rows and chunk rows
    ps = shape["page"]
    T, tables, st, qb, ql = _case(
        shape, [1, 1, ps + 5, 1, 3], [3 * ps, 5, ps // 2, 2 * ps - 1, ps - 2])
    q_lat, q_rope, pages = _latent_operands(shape, T)
    d = tuple(map(jnp.asarray, (tables, st, qb, ql)))
    got = mod.mla_ragged_paged_attention(q_lat, q_rope, pages, *d, layer=1, **kw)
    want = mod.reference_mla_ragged_paged_attention(
        q_lat, q_rope, pages, *d, layer=1, rank=rank)
    out["max_abs_err_vs_oracle"] = float(np.abs(
        np.asarray(got, np.float32) - np.asarray(want, np.float32)).max())
    return out


def latent_child_first(mod, shape: dict, interpret: bool) -> dict:
    """Seconds of each stage of a first dispatch of the latent kernel
    alone, the walk lists built inside it."""
    import jax.numpy as jnp

    from fusioninfer_tpu.engine import aot

    aot.configure_cache(min_compile_seconds=0.0)

    def call(*a):
        return mod.mla_ragged_paged_attention(
            *a, layer=jnp.int32(0), rank=shape["rank"], interpret=interpret)

    out = {}
    q_lens, starts = next(iter(shape["cases"].values()))
    for T in shape["first_T"]:
        _, tables, st, qb, ql = _case(shape, q_lens, starts)
        q_lat, q_rope, pages = _latent_operands(shape, T)
        args = (q_lat, q_rope, pages, *map(jnp.asarray, (tables, st, qb, ql)))
        out[f"t{T}"] = _first_dispatch(call, args)
    return out


def kind_child_time(mod, shape: dict, interpret: bool) -> dict:
    """µs a call of the ragged kernel by layer kind (window) and grid
    (splits), the walk lists built once outside the loop as the engine
    builds them, and the pages each call must read at the HBM peak."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    L = shape["layers"]
    page_us = (2 * shape["KV"] * shape["page"] * shape["Hd"] * 2
               / 819e9 * 1e6)
    out = {}
    for name, (q_lens, starts) in shape["cases"].items():
        T, tables, st, qb, ql = _case(shape, q_lens, starts)
        q, kp, vp = _operands(shape, T)
        d = tuple(map(jnp.asarray, (tables, st, qb, ql)))
        for window in shape["windows"]:
            for splits in shape["splits"]:
                @jax.jit
                def many(q, kp, vp, tables, st, qb, ql, window=window,
                         splits=splits):
                    walks = mod.ragged_walk_lists(
                        q, kp, vp, tables, st, qb, ql, window=window,
                        kv_splits=splits)

                    def body(i, q):
                        kw = dict(interpret=interpret, window=window,
                                  layer=jnp.int32(i % L), walks=walks)
                        if splits:
                            o = mod.ragged_paged_attention_kvsplit(
                                q, kp, vp, tables, st, qb, ql,
                                kv_splits=splits, **kw)
                        else:
                            o = mod.ragged_paged_attention(
                                q, kp, vp, tables, st, qb, ql,
                                coalesce=True, **kw)
                        return o.reshape(q.shape)
                    return jax.lax.fori_loop(0, CALLS, body, q)

                us = _median_us(many, (q, kp, vp, *d))
                nb = T // mod.RAGGED_BLOCK_Q
                _, _, first, end = (np.asarray(a) for a in mod._ragged_walks(
                    d[2], d[3], d[1], nb=nb, block_q=mod.RAGGED_BLOCK_Q,
                    page_size=shape["page"], window=window))
                pages = int((end - first).sum())
                key = (f"{name}.{'full' if window is None else 'window'}"
                       f".splits{splits}")
                out[key] = {"us_per_call": us, "pages_read": pages,
                            "roofline_pct": 100 * pages * page_us / us}
                print(f"  {key}: {us:.1f} us, {pages} pages, "
                      f"{out[key]['roofline_pct']:.1f} % of the memory "
                      "roofline", flush=True)
    return out


def gmm_child_time(_mod, shape: dict, interpret: bool) -> dict:
    """µs a call of the grouped product over one layer of a whole stack,
    by matrix, pass and tiling; the bytes of the touched experts at the
    HBM peak beside it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    from fusioninfer_tpu.models import transformer as tf

    L, G, k = shape["layers"], shape["held"], shape["k"]
    rng = np.random.default_rng(0)
    out = {}
    for K, N in ((shape["D"], shape["F"]), (shape["F"], shape["D"])):
        stack = jax.random.normal(jax.random.key(1), (L * G, K, N),
                                  jnp.bfloat16) * 0.02
        tilings = [tf.gmm_tiling(K, N)] + [
            t for t in GMM_CANDIDATES.get((K, N), []) if t != tf.gmm_tiling(K, N)]
        for name, (tokens, local) in shape["passes"].items():
            A = tokens * k
            sizes = np.bincount(rng.integers(0, G, local), minlength=G)
            xs = jax.random.normal(jax.random.key(2), (A, K), jnp.bfloat16)
            touched = int((sizes > 0).sum())
            for tiling in tilings:
                pad = -A % tiling[0]
                xp = jnp.pad(xs, ((0, pad), (0, 0))) if pad else xs

                @jax.jit
                def many(x, w, sz, tiling=tiling):
                    def body(i, acc):
                        gs = jax.lax.dynamic_update_slice(
                            jnp.zeros((L * G,), jnp.int32), sz, ((i % L) * G,))
                        y = gmm(x, w, gs, preferred_element_type=jnp.bfloat16,
                                tiling=tiling, interpret=interpret)
                        return acc + y[0, 0].astype(jnp.float32)
                    return jax.lax.fori_loop(0, CALLS, body, jnp.float32(0))

                key = f"{K}x{N}.{name}.{'x'.join(map(str, tiling))}"
                try:
                    us = _median_us(many, (xp, stack, jnp.asarray(sizes, jnp.int32)))
                except Exception as e:  # a tiling the chip's VMEM refuses
                    out[key] = {"failed": str(e).splitlines()[0][:200]}
                    print(f"  {key}: failed", flush=True)
                    continue
                least = touched * K * N * 2 / 819e9 * 1e6
                out[key] = {"us_per_call": us, "touched": touched,
                            "rows": int(local), "us_at_hbm_peak": least,
                            "roofline_pct": 100 * least / us}
                print(f"  {key}: {us:.1f} us, {touched} experts touched, "
                      f"{100 * least / us:.1f} % of reading them", flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", action="append", default=[],
                    help="name=path/to/paged_attention.py[@CONST=int[,CONST=int]]")
    ap.add_argument("--no-tree", action="store_true",
                    help="the variants only")
    ap.add_argument("--time-only", action="append", default=[],
                    metavar="NAME", help="no first dispatch for this variant")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: tiny shapes, interpret kernels")
    ap.add_argument("--kv-splits", type=int, default=8,
                    help="0: the single-walk grid, for ROADMAP S2's A/B")
    ap.add_argument("--latent", nargs="?", const="deepseek-v2-ep4",
                    choices=sorted(LATENT_SHAPES),
                    help="the latent (MLA) kernel at this preset's shapes "
                         "(deepseek-v2-ep4 where none is named)")
    ap.add_argument("--gmm", nargs="?", const="longcat-flash-ep32",
                    choices=sorted(GMM_SHAPES),
                    help="the grouped product at this preset's shapes "
                         "(longcat-flash-ep32 where none is named)")
    ap.add_argument("--by-kind", action="store_true",
                    help="the ragged kernel as smallthinker-21b-a3b's full "
                         "and window layers call it, single walk and 8 splits")
    ap.add_argument("--ring", type=int, nargs="*", default=[],
                    help="--latent: the tree's kernel at these ring depths too")
    ap.add_argument("--out", default="chiprun_out/kernel_probe/probe.json")
    ap.add_argument("--child", nargs=3, metavar=("KIND", "NAME", "SPEC"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.gmm:  # jax's own kernel: the tree file named here is not read
        shape = GMM_TINY if args.tiny else GMM_SHAPES[args.gmm]
        tree = LATENT_TREE
        children = {"time": gmm_child_time}
    elif args.by_kind:
        shape, tree = KIND_TINY if args.tiny else KIND_REAL, TREE
        children = {"time": kind_child_time}
    elif args.latent:
        shape = LATENT_TINY if args.tiny else LATENT_SHAPES[args.latent]
        tree = LATENT_TREE
        children = {"time": latent_child_time, "first": latent_child_first}
    else:
        shape, tree = dict(TINY if args.tiny else REAL,
                           kv_splits=args.kv_splits), TREE
        children = {"time": child_time, "first": child_first}

    if args.child:
        import jax

        kind, name, spec = args.child
        if not args.tiny and jax.default_backend() != "tpu":
            raise SystemExit("kernel_probe: no TPU (--tiny rehearses on the CPU)")
        res = children[kind](_load(name, spec), shape, interpret=args.tiny)
        res["device"] = jax.devices()[0].device_kind
        print(json.dumps(res))
        return 0

    variants = ([] if args.no_tree else [("tree", tree)]) + [
        tuple(v.split("=", 1)) for v in args.variant] + [
        (f"ring{n}", f"{tree}@MLA_RING_SLOTS={n}") for n in args.ring]
    report = {"shape": {k: v for k, v in shape.items()},
              "calls": CALLS, "repeats": REPEATS, "variants": {}}
    os.makedirs(os.path.dirname(os.path.join(REPO, args.out)), exist_ok=True)
    for name, spec in variants:
        entry = report["variants"][name] = {"spec": spec}
        # "first" twice: the first process leaves the programs in the
        # persistent cache, the second is the warm start that is reported
        for kind, key in (("time", "time"), ("first", "first_dispatch_cold"),
                          ("first", "first_dispatch")):
            if kind not in children or (
                    kind == "first" and name in args.time_only):
                continue
            print(f"== {name} {key}", flush=True)
            cmd = [sys.executable, os.path.abspath(__file__), "--child",
                   kind, name, spec, "--kv-splits", str(args.kv_splits)] + (
                       ["--tiny"] if args.tiny else []) + (
                       ["--latent", args.latent] if args.latent else []) + (
                       ["--gmm", args.gmm] if args.gmm else []) + (
                       ["--by-kind"] if args.by_kind else [])
            p = subprocess.run(cmd, cwd=REPO, stdout=subprocess.PIPE, text=True)
            lines = p.stdout.strip().splitlines()
            print("\n".join(lines[:-1]), flush=True)
            if p.returncode:
                entry[key] = {"failed": p.returncode}
                continue
            entry[key] = json.loads(lines[-1])
            report["device"] = entry[key].pop("device")
            if kind == "first":
                for prog, v in entry[key].items():
                    print(f"  {prog}: " + ", ".join(
                        f"{k} {x:.3f}" for k, x in v.items()
                        if k.endswith("_s")), flush=True)
        with open(os.path.join(REPO, args.out), "w") as f:
            json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
