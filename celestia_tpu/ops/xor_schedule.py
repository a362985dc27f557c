"""XOR-schedule-compiled Reed-Solomon extend (ADR-024).

The dense spelling pays the full (8k x 8k) GF(2) contraction per tile
(rs_pallas._encode_math / rs_tpu.rs_encode_rows) even though the
expanded Leopard matrix is ~50% zeros and its parity rows share large
common subexpressions. The XOR erasure-coding literature (2108.02692
program-optimized XOR codes; 1701.07731 polynomial-ring transforms)
spells such codes as straight-line XOR programs instead: every parity
bit-plane is a XOR of input bit-planes, and a compile pass hoists
subexpressions shared across rows so each is computed once.

This module is that compile pass plus its evaluators:

  * `compile_schedule(k)` lowers rs_tpu.encode_bit_matrix(k) into an
    `XorSchedule` — a topologically ordered straight-line program of
    `dst ^= src` plane ops with common pairs hoisted into shared nodes
    (greedy pair-counting, the Paar construction 2108.02692 builds on),
    cached per k like the `_jitted_*` builders it feeds.
  * pure-jnp spellings (`apply_planes`, `rs_encode_rows_xor`,
    `extend_square_xor`) — the XLA/reference/interpret path, and the
    spelling the row-sharded mesh program evaluates with per-shard
    column-block schedules (`sharded_schedule_arrays`).
  * `encode2d_xor_hash` — the Pallas kernel: the SAME fused hash
    pipeline as rs_pallas.encode2d_hash (parity bytes feed the NMT leaf
    SHA-256 without leaving VMEM), with the MXU matmul replaced by the
    schedule's gather+XOR levels on the VPU.
  * `apply_planes_np` — the numpy evaluator the property tests and
    `make xor-smoke` pin against the dense matmul, byte for byte.

Schedule format (the contract specs/da_pipeline.md documents): planes
are indexed inputs [0, n_in), a constant zero plane at n_in (the pad
target), then CSE nodes in topological level order. Levels are stored
flattened — `flat_a`/`flat_b` hold each node's two operand indices and
`level_widths` the static per-level split — so one schedule object
serves the unrolled single-device evaluator (indices as constants) and
the mesh evaluator (indices as sharded operands) identically. Rows
assemble from `row_idx` (n_out, width), ZERO-padded.

Routing: extend_tpu._xor_active decides per k from the measured
crossover table (config/xor_schedule.json, app/calibration) with the
CELESTIA_XOR_SCHEDULE env override, exactly like _fused_active — and
the dense spelling remains the byte-identical fallback either way.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from celestia_tpu import devledger, tracing
from celestia_tpu.appconsts import SHARE_SIZE
from celestia_tpu.ops import rs_tpu

# CSE node budget per compile: diminishing returns set in well before
# 4·(8k) nodes, and the budget bounds both compile time (O(cols) per
# node) and the pair-count workspace ((cols+budget)^2 int32).
_MAX_NODES_FACTOR = 4
_MAX_NODES_CAP = 4096
# a pair must appear in at least this many rows to be worth a node
# (count c saves c-1 XORs; 2 is the break-even the Paar greedy uses)
_MIN_PAIR_COUNT = 2


@dataclasses.dataclass(frozen=True, eq=False)
class XorSchedule:
    """A compiled straight-line XOR program over bit-planes.

    Plane index space: [0, n_in) inputs, n_in the constant zero plane,
    then n_nodes CSE nodes appended level by level. Node t computes
    planes[flat_a[t]] ^ planes[flat_b[t]]; `level_widths` splits the
    flat node list into topological levels whose members are mutually
    independent (operands always come from earlier levels), so each
    level evaluates as one batched gather+XOR. Output row r is the XOR
    of planes[row_idx[r, :]] (ZERO-padded to the common width)."""

    n_in: int
    n_out: int
    level_widths: tuple[int, ...]
    flat_a: np.ndarray  # (n_nodes,) int32 operand indices
    flat_b: np.ndarray  # (n_nodes,) int32
    row_idx: np.ndarray  # (n_out, width) int32, ZERO-padded
    n_nodes: int
    xor_ops: int  # scheduled XORs: n_nodes + sum(row nnz - 1)
    cse_hits: int  # row substitutions the hoisted nodes serve
    dense_ops: int  # popcount(m2) - n_out: the naive per-row XOR count

    @property
    def zero(self) -> int:
        return self.n_in


def _greedy_pair_cse(m2: np.ndarray, max_nodes: int):
    """Greedy pair-counting CSE (Paar): repeatedly hoist the operand
    pair co-occurring in the most rows into a fresh node.

    The pair-count matrix is maintained incrementally — hoisting (i, j)
    into node n only changes counts involving i, j, n, an O(cols)
    update — and the argmax rides lazily-refreshed per-column upper
    bounds, so each node costs O(cols) instead of O(cols^2).

    Returns (nodes, rows, cse_hits): nodes as (a, b) pairs in creation
    order (node t lives at column n_in + t), rows as per-output index
    lists over the extended column space."""
    n_out, n_in = m2.shape
    cap = n_in + max_nodes
    m = np.zeros((n_out, cap), dtype=bool)
    m[:, :n_in] = m2 != 0
    cnt = np.zeros((cap, cap), dtype=np.int32)
    act = m[:, :n_in].astype(np.int32)
    cnt[:n_in, :n_in] = act.T @ act
    np.fill_diagonal(cnt, 0)
    colmax = cnt.max(axis=1)
    nodes: list[tuple[int, int]] = []
    cse_hits = 0
    while len(nodes) < max_nodes:
        # lazy argmax: colmax rows only ever go stale HIGH (decrements
        # to cnt[x, i/j] are not propagated), so refreshing the current
        # winner until its bound is exact finds the true maximum
        while True:
            i = int(np.argmax(colmax))
            j = int(np.argmax(cnt[i]))
            v = int(cnt[i, j])
            if v >= colmax[i]:
                break
            colmax[i] = v
        if v < _MIN_PAIR_COUNT:
            break
        n = n_in + len(nodes)
        rows = np.nonzero(m[:, i] & m[:, j])[0]
        s0 = m[rows].sum(axis=0).astype(np.int32)  # per-col count over rows
        m[rows, i] = False
        m[rows, j] = False
        m[rows, n] = True
        # count deltas: removing i (and j) from `rows` drops s0[x]
        # co-occurrences for every column x; adding n gains them (with
        # i, j gone). The {i, j, n} cross entries are exactly zero after
        # the substitution (no row keeps i or j alongside n).
        s1 = s0.copy()
        s1[i] = 0
        s1[j] = 0
        for c, delta in ((i, -s0), (j, -s0), (n, s1)):
            cnt[c, :] += delta
            cnt[:, c] += delta
        for a in (i, j, n):
            for b in (i, j, n):
                cnt[a, b] = 0
        colmax = np.maximum(colmax, cnt[:, n])
        for c in (i, j, n):
            colmax[c] = cnt[c].max()
        nodes.append((int(i), int(j)))
        cse_hits += len(rows)
    ncols = n_in + len(nodes)
    out_rows = [np.nonzero(m[r, :ncols])[0] for r in range(n_out)]
    return nodes, out_rows, cse_hits


def _compile_from_matrix(m2: np.ndarray) -> XorSchedule:
    """Lower a 0/1 matrix (parity = m2 @ bits mod 2) into an XorSchedule."""
    m2 = np.asarray(m2, dtype=np.uint8)
    n_out, n_in = m2.shape
    max_nodes = min(_MAX_NODES_FACTOR * n_in, _MAX_NODES_CAP)
    nodes, rows, cse_hits = _greedy_pair_cse(m2, max_nodes)

    # topological levels: node depth = 1 + max(operand depths); inputs
    # (and the zero plane) are depth 0. Creation order already respects
    # dependencies, so one forward pass assigns depths.
    depth = np.zeros(n_in + len(nodes), dtype=np.int32)
    for t, (a, b) in enumerate(nodes):
        depth[n_in + t] = 1 + max(depth[a], depth[b])
    n_levels = int(depth.max()) if len(nodes) else 0
    by_level: list[list[int]] = [[] for _ in range(n_levels)]
    for t in range(len(nodes)):
        by_level[depth[n_in + t] - 1].append(t)

    # reindex into the evaluation layout: inputs, ZERO at n_in, then
    # nodes level by level (creation order within a level)
    zero = n_in
    remap = np.zeros(n_in + len(nodes), dtype=np.int32)
    remap[:n_in] = np.arange(n_in)
    pos = n_in + 1
    for lvl in by_level:
        for t in lvl:
            remap[n_in + t] = pos
            pos += 1
    flat_a = np.array(
        [remap[nodes[t][0]] for lvl in by_level for t in lvl], dtype=np.int32
    )
    flat_b = np.array(
        [remap[nodes[t][1]] for lvl in by_level for t in lvl], dtype=np.int32
    )
    level_widths = tuple(len(lvl) for lvl in by_level)

    width = max((len(r) for r in rows), default=1) or 1
    row_idx = np.full((n_out, width), zero, dtype=np.int32)
    for r, cols in enumerate(rows):
        row_idx[r, : len(cols)] = remap[cols]

    return XorSchedule(
        n_in=n_in,
        n_out=n_out,
        level_widths=level_widths,
        flat_a=flat_a,
        flat_b=flat_b,
        row_idx=row_idx,
        n_nodes=len(nodes),
        xor_ops=len(nodes) + int(sum(max(len(r) - 1, 0) for r in rows)),
        cse_hits=cse_hits,
        dense_ops=int(m2.sum()) - n_out,
        )


def supported(k: int) -> bool:
    """The schedule compiler covers every committed square size: any
    power-of-two k the Leopard matrix itself exists for."""
    return 1 <= k <= 256 and (k & (k - 1)) == 0


@functools.lru_cache(maxsize=16)
def compile_schedule(k: int) -> XorSchedule:
    """The per-k schedule for the full (8k, 8k) encode matrix, compiled
    once per process (trace-time; the jit caches that consume it are
    also per-k, so this is the `_jitted_*` caching discipline)."""
    with tracing.span("extend.xor_compile", k=k):
        return _compile_from_matrix(rs_tpu.encode_bit_matrix(k))


@functools.lru_cache(maxsize=64)
def compile_col_block(k: int, sp: int, idx: int) -> XorSchedule:
    """Schedule for shard `idx` of the row-sharded mesh path: the
    (8k, 8k/sp) column block of the encode matrix that contracts
    against the 8k/sp bit-planes this shard owns. Partial parities XOR
    across shards (int8 psum mod 2 — XOR is GF(2) addition), exactly
    like the dense spelling's partial counts."""
    m2 = rs_tpu.encode_bit_matrix(k)
    cols = (8 * k) // sp
    return _compile_from_matrix(m2[:, idx * cols : (idx + 1) * cols])


def schedule_stats(k: int) -> dict:
    """Host-readable schedule metrics (stamped into bench results by
    bench.py --xor-schedule)."""
    s = compile_schedule(k)
    return {
        "schedule_xor_ops": s.xor_ops,
        "schedule_cse_hits": s.cse_hits,
        "schedule_dense_ops": s.dense_ops,
        "schedule_nodes": s.n_nodes,
        "schedule_levels": len(s.level_widths),
        "schedule_row_width": int(s.row_idx.shape[1]),
    }


# ------------------------------------------------------------------ #
# Evaluators. One spelling, three callers: jnp with constant indices
# (single-device XLA + the Pallas kernel's tile math), jnp with traced
# indices (the mesh path's sharded schedule operands), numpy (tests).


def apply_planes(planes, sched: XorSchedule,
                 flat_a=None, flat_b=None, row_idx=None):
    """(n_in, T) 0/1 planes -> (n_out, T) parity planes, any int dtype.

    The index arrays default to the schedule's own (trace-time
    constants); the mesh path passes its per-shard traced operands with
    the SAME static level_widths/row width, so both spellings trace
    through this one body."""
    flat_a = sched.flat_a if flat_a is None else flat_a
    flat_b = sched.flat_b if flat_b is None else flat_b
    row_idx = sched.row_idx if row_idx is None else row_idx
    zero = jnp.zeros((1, planes.shape[-1]), planes.dtype)
    acc = jnp.concatenate([planes, zero], axis=0)
    off = 0
    for w in sched.level_widths:
        new = jnp.take(acc, flat_a[off : off + w], axis=0) ^ jnp.take(
            acc, flat_b[off : off + w], axis=0
        )
        acc = jnp.concatenate([acc, new], axis=0)
        off += w
    # row assembly as a fori_loop over the padded width: unrolling the
    # (up to ~240 at k=128) per-slot gathers blows up the HLO and XLA
    # compile time; the loop body compiles once
    row_idx = jnp.asarray(row_idx)
    out = jnp.take(acc, row_idx[:, 0], axis=0)
    if row_idx.shape[1] > 1:
        def _body(t, o):
            idx = jax.lax.dynamic_index_in_dim(
                row_idx, t, axis=1, keepdims=False
            )
            return o ^ jnp.take(acc, idx, axis=0)

        out = jax.lax.fori_loop(1, row_idx.shape[1], _body, out)
    return out


def apply_planes_np(planes: np.ndarray, sched: XorSchedule) -> np.ndarray:
    """Numpy spelling of apply_planes (property tests, xor-smoke)."""
    acc = np.concatenate(
        [planes, np.zeros((1, planes.shape[-1]), planes.dtype)], axis=0
    )
    off = 0
    for w in sched.level_widths:
        a = sched.flat_a[off : off + w]
        b = sched.flat_b[off : off + w]
        acc = np.concatenate([acc, acc[a] ^ acc[b]], axis=0)
        off += w
    out = acc[sched.row_idx[:, 0]].copy()
    for t in range(1, sched.row_idx.shape[1]):
        out ^= acc[sched.row_idx[:, t]]
    return out


def _xor_encode_math(x, sched: XorSchedule,
                     flat_a=None, flat_b=None, row_idx=None):
    """The schedule's tile math, pure jnp: (k, T) uint8 data -> (k, T)
    uint8 parity. Unpack/pack spelling is byte-for-byte the one in
    rs_pallas._encode_math, so the dense and XOR paths differ ONLY in
    the contraction between them. This EXACT body is what the Pallas
    kernel runs on its VMEM tile (index arrays as kernel operands) and
    what the eager reference spelling executes (trace-time constants)."""
    k = x.shape[0]
    xi = x.astype(jnp.int32)  # (k, T)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (k, 8, x.shape[-1]), 1)
    bits = ((xi[:, None, :] >> shifts) & 1).reshape(8 * k, x.shape[-1])
    pbits = apply_planes(
        bits, sched, flat_a=flat_a, flat_b=flat_b, row_idx=row_idx
    ).reshape(k, 8, x.shape[-1])
    packed = (pbits << shifts).sum(axis=1)
    return packed.astype(jnp.uint8)


def rs_encode_rows_xor(data: jnp.ndarray, sched: XorSchedule) -> jnp.ndarray:
    """Schedule spelling of rs_tpu.rs_encode_rows: (..., k, B) uint8 ->
    (..., k, B) parity; second-to-last axis is the shard axis."""
    bits = rs_tpu.unpack_bits(data)  # (..., 8k, B) int8
    planes = jnp.moveaxis(bits, -2, 0)
    lanes_shape = planes.shape[1:]
    flat = planes.reshape(planes.shape[0], -1).astype(jnp.int32)
    out = apply_planes(flat, sched)
    out = jnp.moveaxis(out.reshape(out.shape[0], *lanes_shape), 0, -2)
    return rs_tpu.pack_bits(out & 1)


def extend_square_xor(q0: jnp.ndarray, sched: XorSchedule) -> jnp.ndarray:
    """Schedule spelling of rs_tpu.extend_square: (k, k, 512) -> EDS,
    same quadrant chain (Q1 = row-extend Q0, Q2 = col-extend Q0,
    Q3 = row-extend Q2)."""
    q1 = rs_encode_rows_xor(q0, sched)
    q2 = jnp.swapaxes(rs_encode_rows_xor(jnp.swapaxes(q0, 0, 1), sched), 0, 1)
    q3 = rs_encode_rows_xor(q2, sched)
    top = jnp.concatenate([q0, q1], axis=1)
    bottom = jnp.concatenate([q2, q3], axis=1)
    return jnp.concatenate([top, bottom], axis=0)


# ------------------------------------------------------------------ #
# Row-sharded spelling: per-shard column-block schedules ride the mesh
# program as 'sp'-sharded operands (a shard_map traces ONE program for
# all devices, so per-device constants are impossible — but per-device
# *data* is exactly what sharded operands are).


@functools.lru_cache(maxsize=16)
def sharded_schedule_arrays(k: int, sp: int):
    """Stack the sp column-block schedules into common-shape arrays.

    Per-level widths and the row width are padded to the max across
    shards (pad nodes compute ZERO ^ ZERO; pad row slots reference
    ZERO — both byte-neutral). Returns (level_widths, flat_a, flat_b,
    row_idx) with flat_a/flat_b (sp, sum(level_widths)) and row_idx
    (sp, 8k, width) int32, plus a template XorSchedule carrying the
    static level structure for apply_planes."""
    scheds = [compile_col_block(k, sp, i) for i in range(sp)]
    n_in = scheds[0].n_in
    zero = n_in
    n_levels = max(len(s.level_widths) for s in scheds)
    widths = tuple(
        max(
            (s.level_widths[l] if l < len(s.level_widths) else 0)
            for s in scheds
        )
        for l in range(n_levels)
    )
    total = sum(widths)
    flat_a = np.full((sp, total), zero, dtype=np.int32)
    flat_b = np.full((sp, total), zero, dtype=np.int32)
    row_w = max(s.row_idx.shape[1] for s in scheds)
    row_idx = np.full((sp, scheds[0].n_out, row_w), zero, dtype=np.int32)
    for i, s in enumerate(scheds):
        # node indices shift when levels pad: remap this shard's layout
        # (n_in+1 + own level offsets) into the padded layout
        remap = np.arange(n_in + 1 + s.n_nodes, dtype=np.int32)
        src = n_in + 1
        dst = n_in + 1
        for l, w_pad in enumerate(widths):
            w = s.level_widths[l] if l < len(s.level_widths) else 0
            remap[src : src + w] = np.arange(dst, dst + w, dtype=np.int32)
            src += w
            dst += w_pad
        off = 0
        src = 0
        for l, w_pad in enumerate(widths):
            w = s.level_widths[l] if l < len(s.level_widths) else 0
            flat_a[i, off : off + w] = remap[s.flat_a[src : src + w]]
            flat_b[i, off : off + w] = remap[s.flat_b[src : src + w]]
            off += w_pad
            src += w
        row_idx[i, :, : s.row_idx.shape[1]] = remap[s.row_idx]
    template = dataclasses.replace(
        scheds[0],
        level_widths=widths,
        flat_a=flat_a[0],
        flat_b=flat_b[0],
        row_idx=row_idx[0],
    )
    return template, flat_a, flat_b, row_idx


# ------------------------------------------------------------------ #
# Pallas kernel: the fused extend+hash pipeline of rs_pallas with the
# MXU contraction swapped for the schedule (ADR-024). Everything after
# the parity pack — leaf message build, unrolled SHA-256 — is shared
# with rs_pallas (_leaf_digest_math), so the hash bytes cannot diverge
# between the dense and XOR kernels.


def _sched_operands(sched: XorSchedule):
    """The schedule's index arrays in kernel-operand shape: Pallas
    kernels cannot capture array constants, and 1-D operands don't tile
    on TPU, so flat_a/flat_b ride as (1, n_nodes)."""
    return sched.flat_a[None], sched.flat_b[None], sched.row_idx


def _sched_in_specs(sched: XorSchedule, pl):
    """Replicated (every grid step sees the whole array) BlockSpecs for
    the three index operands."""
    return [
        pl.BlockSpec((1, sched.n_nodes), lambda i: (0, 0)),
        pl.BlockSpec((1, sched.n_nodes), lambda i: (0, 0)),
        pl.BlockSpec(sched.row_idx.shape, lambda i: (0, 0)),
    ]


def _xor_encode_kernel(x_ref, a_ref, b_ref, r_ref, o_ref, *,
                       sched: XorSchedule):
    o_ref[...] = _xor_encode_math(
        x_ref[...], sched,
        flat_a=a_ref[0], flat_b=b_ref[0], row_idx=r_ref[...],
    )


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("xor.encode")
def _xor_encode_call(k: int, n: int, interpret: bool):
    from jax.experimental import pallas as pl

    from celestia_tpu.ops import rs_pallas

    grid, tile = rs_pallas._grid_tile(n)
    sched = compile_schedule(k)
    kernel = functools.partial(_xor_encode_kernel, sched=sched)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((k, tile), lambda i: (0, i))]
        + _sched_in_specs(sched, pl),
        out_specs=pl.BlockSpec((k, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.uint8),
        interpret=interpret,
    )


def encode2d_xor(x2: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Encode-only XOR-schedule kernel (no hash stage) — the spelling
    interpret-mode tests exercise, mirroring rs_pallas.encode2d."""
    k, n = x2.shape
    return _xor_encode_call(k, n, interpret)(
        x2, *_sched_operands(compile_schedule(k))
    )


def _xor_fused_kernel(x_ref, a_ref, b_ref, r_ref, o_ref, d_ref, *,
                      sched: XorSchedule):
    from celestia_tpu.ops import rs_pallas

    packed = _xor_encode_math(
        x_ref[...], sched,
        flat_a=a_ref[0], flat_b=b_ref[0], row_idx=r_ref[...],
    )
    o_ref[...] = packed
    k, t = packed.shape
    rs_pallas._store_digests(d_ref, rs_pallas._leaf_digest_math(
        packed, rs_pallas._parity_prefix(k * (t // SHARE_SIZE))
    ))


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("xor.fused")
def _xor_fused_call(k: int, n: int, interpret: bool):
    from jax.experimental import pallas as pl

    from celestia_tpu.ops import rs_pallas

    grid, tile = rs_pallas._grid_tile(n)
    nct = tile // SHARE_SIZE
    sched = compile_schedule(k)
    kernel = functools.partial(_xor_fused_kernel, sched=sched)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((k, tile), lambda i: (0, i))]
        + _sched_in_specs(sched, pl),
        out_specs=[
            pl.BlockSpec((k, tile), lambda i: (0, i)),
            rs_pallas._digest_spec(pl, k, nct),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, n), jnp.uint8),
            rs_pallas._digest_shape(k, n),
        ],
        interpret=interpret,
    )


def fused_supported(k: int, n_lanes: int) -> bool:
    """The XOR kernel rides the same grid/tile constraints as the dense
    fused kernel (whole cells per tile), plus schedule coverage."""
    from celestia_tpu.ops import rs_pallas

    return supported(k) and rs_pallas.fused_supported(k, n_lanes)


def encode2d_xor_hash(x2: jnp.ndarray, interpret: bool = False):
    """Fused XOR-schedule encode + NMT leaf hash: (k, N) uint8 data
    shards -> ((k, N) parity, (k, N/512, 8) uint32 leaf digest words).
    Same output contract as rs_pallas.encode2d_hash — the parity bytes
    feed the SHA stage without leaving VMEM; only the contraction
    spelling differs."""
    from celestia_tpu.ops import rs_pallas

    k, n = x2.shape
    parity, d = _xor_fused_call(k, n, interpret)(
        x2, *_sched_operands(compile_schedule(k))
    )
    return parity, rs_pallas._digests_to_cells(d, k)


def encode2d_xor_hash_reference(x2, tile=None):
    """Eager spelling of encode2d_xor_hash for CPU parity tests (tile
    override as in rs_pallas.encode2d_hash_reference)."""
    from celestia_tpu.ops import rs_pallas

    x2 = jnp.asarray(x2)
    k, n = x2.shape
    sched = compile_schedule(k)
    if tile is None:
        grid, tile = rs_pallas._grid_tile(n)
    else:
        assert n % tile == 0 and tile % SHARE_SIZE == 0
        grid = n // tile
    parity, digests = [], []
    for i in range(grid):
        xt = x2[:, i * tile : (i + 1) * tile]
        p = _xor_encode_math(xt, sched)
        parity.append(np.asarray(p))
        digests.append(jnp.stack(rs_pallas._leaf_digest_math(
            p, rs_pallas._parity_prefix(k * (tile // SHARE_SIZE))
        )))
    return (
        np.concatenate(parity, axis=1),
        np.asarray(rs_pallas._digests_to_cells(jnp.stack(digests), k)),
    )
