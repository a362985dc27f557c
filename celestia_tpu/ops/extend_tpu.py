"""The fused TPU hot path: share square -> EDS -> NMT roots -> DAH hash.

This is the flagship pipeline of the framework — the TPU-native equivalent
of the reference's ExtendBlock chain (app/extend_block.go:14 ->
pkg/da/data_availability_header.go:44,65 -> rsmt2d + pkg/wrapper NMTs),
jitted end-to-end so XLA fuses RS encode, leaf construction, SHA-256 and
the tree reductions without host round-trips.

Structure exploited on-device:

- Both tree families hash the *same* leaves: the wrapper's namespace rule
  (pkg/wrapper/nmt_wrapper.go:93-114 — Q0 cells keep their own namespace,
  parity cells use the parity namespace) depends only on the cell, not on
  whether it is read row-wise or column-wise. So leaf digests are computed
  once over the (2k, 2k) grid and reduced along axis 1 (row trees) and
  axis 0 (column trees).
- Axis length 2k is a power of two, so the RFC-6962 split (largest power
  of two < n) degenerates to a perfectly balanced binary tree:
  level-synchronous pairwise reduction with static shapes at every level.
- Namespace min/max propagation follows nmt v0.20 with IgnoreMaxNamespace.
  The device kernel uses the two-branch specialization
  (min = left.min; max = left.max if right.min == parity else right.max),
  which is provably equal to the general three-branch hasher
  (ops/nmt_host.hash_node) on every tree whose leaf namespaces are
  non-decreasing — the invariant nmt itself enforces via
  ErrInvalidPushOrder/ErrUnorderedSiblings, and which the square builder
  guarantees (Q0 sorted by construction, parity in Q1/Q2/Q3).
  tests/test_nmt_semantics.py pins host/device agreement on adversarial
  vectors including max-namespace leaves inside Q0.

Outputs are byte-identical to celestia_tpu.da (host) and therefore to the
reference DAH.
"""

from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from celestia_tpu import devledger, faults, integrity
from celestia_tpu import namespace as ns
from celestia_tpu import tracing
from celestia_tpu.appconsts import (
    CONTINUATION_SPARSE_SHARE_CONTENT_SIZE as CONT_SPARSE,
    FIRST_SPARSE_SHARE_CONTENT_SIZE as FIRST_SPARSE,
    NAMESPACE_SIZE,
    SHARE_SIZE,
)
from celestia_tpu.ops import rs_tpu
# The pipeline's hasher is the XLA scan spelling. A Pallas alternative
# exists (ops/sha256_pallas.py) and measures 1.8x FASTER standalone on
# the k=128 leaf workload (3.0 vs 5.5 ms for 65k x 571 B messages) —
# but swapping it into THIS fused pipeline measured SLOWER end-to-end
# (k=128 extend 5.97 vs 4.98 ms, NMT-only 4.02 vs 2.7 ms): the
# pallas_call boundary forces the padded/transposed message tensor
# (~38 MB) to materialize in HBM, while XLA fuses leaf construction
# straight into the hash rounds and never builds it. Same lesson as
# ops/rs_pallas (see its docstring): on this pipeline, fusion beats
# hand-tiling — both kernels stay as explicitly-invoked, bit-exact
# alternatives for workloads that feed from HBM anyway.
from celestia_tpu.ops.sha256_jax import sha256_fixed, words_to_bytes

_PARITY_NS = np.frombuffer(ns.PARITY_SHARES_NAMESPACE.bytes, dtype=np.uint8)

# Fused Pallas extend+hash (ADR-019): on an accelerator backend the
# roots pipeline runs ops/rs_pallas.encode2d_hash — parity bytes AND
# NMT leaf digests leave each kernel invocation together, so neither
# the unpacked bit planes nor the padded leaf-message tensor ever
# round-trips through HBM. "0"/"off" pins the XLA spelling (A/B
# benching, bisection); "1"/"on" forces the kernels even on the CPU
# backend — device-backend experiments only: Mosaic does not lower on
# XLA:CPU and the unrolled SHA graph takes minutes to compile there.
# The decision is frozen into each jit cache entry at first trace.
_FUSED_ENV = "CELESTIA_FUSED_KERNELS"


def _fused_active(k: int) -> bool:
    from celestia_tpu.ops import rs_pallas

    v = os.environ.get(_FUSED_ENV, "").strip().lower()
    if v in ("0", "off", "false"):
        return False
    if not rs_pallas.fused_supported(k, k * SHARE_SIZE):
        return False
    if v in ("1", "on", "true"):
        return True
    return jax.default_backend() not in ("cpu",)


# XOR-schedule contraction (ADR-024): per-k choice between the dense
# GF(2) bit-matmul and the sparse CSE-shared XOR schedule, resolved
# from the measured A/B table (config/xor_schedule.json, bench.py
# --xor-schedule) — the two spellings are byte-identical, so this is
# purely a perf decision. "0"/"off" pins dense, "1"/"on" pins the
# schedule; default consults the table (absent/unmeasured -> dense).
# Like _fused_active, the decision freezes into each jit cache entry
# at first trace.
_XOR_ENV = "CELESTIA_XOR_SCHEDULE"


def _xor_active(k: int) -> bool:
    from celestia_tpu.ops import xor_schedule

    v = os.environ.get(_XOR_ENV, "").strip().lower()
    if v in ("0", "off", "false"):
        return False
    if not xor_schedule.supported(k):
        return False
    if v in ("1", "on", "true"):
        return True
    from celestia_tpu.app import calibration

    return calibration.xor_winner(k) == "xor"
_LEAF_PREFIX = np.array([0], dtype=np.uint8)
_NODE_PREFIX = np.array([1], dtype=np.uint8)
NMT_NODE_SIZE = 2 * NAMESPACE_SIZE + 32  # 90


def _bcast_const(const: np.ndarray, batch_shape: tuple[int, ...]) -> jnp.ndarray:
    return jnp.broadcast_to(jnp.asarray(const), (*batch_shape, const.shape[0]))


def nmt_leaf_nodes(leaf_ns: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """(..., 29) ns + (..., D) data -> (..., 90) NMT leaf nodes."""
    batch = data.shape[:-1]
    msg = jnp.concatenate([_bcast_const(_LEAF_PREFIX, batch), leaf_ns, data], axis=-1)
    digest = sha256_fixed(msg)
    return jnp.concatenate([leaf_ns, leaf_ns, digest], axis=-1)


def _nmt_reduce_once(nodes: jnp.ndarray) -> jnp.ndarray:
    """One pairwise NMT level: (..., n, 90) -> (..., n/2, 90)."""
    parity = jnp.asarray(_PARITY_NS)
    left = nodes[..., 0::2, :]
    right = nodes[..., 1::2, :]
    batch = left.shape[:-1]
    msg = jnp.concatenate([_bcast_const(_NODE_PREFIX, batch), left, right], axis=-1)
    digest = sha256_fixed(msg)
    min_ns = left[..., :NAMESPACE_SIZE]
    right_is_parity = jnp.all(
        right[..., :NAMESPACE_SIZE] == parity, axis=-1, keepdims=True
    )
    max_ns = jnp.where(
        right_is_parity,
        left[..., NAMESPACE_SIZE : 2 * NAMESPACE_SIZE],
        right[..., NAMESPACE_SIZE : 2 * NAMESPACE_SIZE],
    )
    return jnp.concatenate([min_ns, max_ns, digest], axis=-1)


def nmt_reduce_axis(nodes: jnp.ndarray) -> jnp.ndarray:
    """Pairwise-reduce (..., n, 90) NMT nodes along axis -2 to roots (..., 90).

    n must be a power of two (always true for EDS axes).
    """
    while nodes.shape[-2] > 1:
        nodes = _nmt_reduce_once(nodes)
    return nodes[..., 0, :]


def nmt_reduce_levels(nodes: jnp.ndarray) -> list[jnp.ndarray]:
    """Like nmt_reduce_axis, but KEEP every tree level: returns
    [leaves (..., n, 90), (..., n/2, 90), ..., root level (..., 1, 90)].

    Every (lo, hi) range the RFC-6962 split structure visits on a
    power-of-two tree is one of these aligned nodes, so the level stack
    is exactly the memo proof.NmtRowProver builds on host — device-
    computed here once, then served as pure byte lookups (ADR-019)."""
    levels = [nodes]
    while nodes.shape[-2] > 1:
        nodes = _nmt_reduce_once(nodes)
        levels.append(nodes)
    return levels


def merkle_root_pow2(items: jnp.ndarray) -> jnp.ndarray:
    """RFC-6962 merkle root of (..., n, D) items, n a power of two.

    Matches tendermint merkle.HashFromByteSlices for power-of-two counts
    (pkg/da/data_availability_header.go:92-108 hashes 4k axis roots).
    """
    batch = items.shape[:-1]
    leaves = sha256_fixed(
        jnp.concatenate([_bcast_const(_LEAF_PREFIX, batch), items], axis=-1)
    )
    while leaves.shape[-2] > 1:
        left = leaves[..., 0::2, :]
        right = leaves[..., 1::2, :]
        msg = jnp.concatenate(
            [_bcast_const(_NODE_PREFIX, left.shape[:-1]), left, right], axis=-1
        )
        leaves = sha256_fixed(msg)
    return leaves[..., 0, :]


def _leaf_namespaces(q0_ns: jnp.ndarray, k: int) -> jnp.ndarray:
    """(k, k, 29) Q0 namespaces -> (2k, 2k, 29) per-cell leaf namespaces."""
    parity = jnp.broadcast_to(jnp.asarray(_PARITY_NS), (k, k, NAMESPACE_SIZE))
    top = jnp.concatenate([q0_ns, parity], axis=1)
    bottom = jnp.concatenate([parity, parity], axis=1)
    return jnp.concatenate([top, bottom], axis=0)


def nmt_roots_of_eds(eds: jnp.ndarray, leaf_ns: jnp.ndarray):
    """(2k,2k,512) EDS + per-cell leaf namespaces -> (row_roots, col_roots).

    Row and column trees are reduced in ONE level-synchronous pass (stacked
    on a leading axis): the serial depth of the hot path is log2(2k) tree
    levels total instead of 2x that, and every level runs with twice the
    lanes — the latency-bound top levels are where that matters.
    """
    leaf_nodes = nmt_leaf_nodes(leaf_ns, eds)  # (2k, 2k, 90)
    stacked = jnp.stack([leaf_nodes, jnp.swapaxes(leaf_nodes, 0, 1)], axis=0)
    roots = nmt_reduce_axis(stacked)  # (2, 2k, 90)
    return roots[0], roots[1]


def _digest_grid_roots(digest_bytes: jnp.ndarray, leaf_ns: jnp.ndarray):
    """(2k,2k,32) per-cell leaf digests + (2k,2k,29) namespaces ->
    (row_roots, col_roots). The digest of cell (r, c) is the same leaf
    digest in its row tree and its column tree (the namespace rule
    depends only on the cell), so one grid feeds both reductions —
    stacked into the same level-synchronous pass as nmt_roots_of_eds."""
    leaf_nodes = jnp.concatenate([leaf_ns, leaf_ns, digest_bytes], axis=-1)
    stacked = jnp.stack([leaf_nodes, jnp.swapaxes(leaf_nodes, 0, 1)], axis=0)
    roots = nmt_reduce_axis(stacked)
    return roots[0], roots[1]


def _roots_of_fused(shares: jnp.ndarray, m2: jnp.ndarray,
                    interpret: bool = False, xor: bool = False):
    """The Pallas spelling of _roots_of (ADR-019): the three quadrant
    encodes run ops/rs_pallas.encode2d_hash, so every parity cell's NMT
    leaf digest is computed in VMEM next to the pack stage; Q0 cells go
    through the companion leaf_digests2d kernel. Only the EDS bytes and
    the (2k)²·32 B digest grid reach HBM — the unpacked bit planes and
    the 542-byte leaf messages never do. Quadrant chain and digest
    orientation follow rs_pallas.extend_square: column extension is the
    kernel's native layout, row extension transposes in and out (and the
    digest grids transpose with it)."""
    from celestia_tpu.ops import rs_pallas

    if xor:
        # Same fused pipeline, XOR-schedule contraction (ADR-024): the
        # hash stage and output contract are shared with the dense
        # kernel, so only the encode spelling changes.
        from celestia_tpu.ops import xor_schedule

        def _enc(x, _m2, inter):
            return xor_schedule.encode2d_xor_hash(x, inter)
    else:
        _enc = rs_pallas.encode2d_hash

    k = shares.shape[0]
    n = k * SHARE_SIZE
    x0 = shares.reshape(k, n)
    q0_ns = shares[..., :NAMESPACE_SIZE]
    d0 = rs_pallas.leaf_digests2d(
        x0, rs_pallas.pad_namespaces(q0_ns), interpret
    )  # (k, k, 8): [row, col]
    q2f, d2 = _enc(x0, m2, interpret)  # native: [row, col]
    q2 = q2f.reshape(k, k, SHARE_SIZE)
    x0t = jnp.swapaxes(shares, 0, 1).reshape(k, n)
    q1t, d1t = _enc(x0t, m2, interpret)  # [col, row]
    q1 = jnp.swapaxes(q1t.reshape(k, k, SHARE_SIZE), 0, 1)
    q2t = jnp.swapaxes(q2, 0, 1).reshape(k, n)
    q3t, d3t = _enc(q2t, m2, interpret)  # [col, row]
    q3 = jnp.swapaxes(q3t.reshape(k, k, SHARE_SIZE), 0, 1)
    eds = jnp.concatenate([
        jnp.concatenate([shares, q1], axis=1),
        jnp.concatenate([q2, q3], axis=1),
    ], axis=0)
    dig = jnp.concatenate([
        jnp.concatenate([d0, jnp.swapaxes(d1t, 0, 1)], axis=1),
        jnp.concatenate([d2, jnp.swapaxes(d3t, 0, 1)], axis=1),
    ], axis=0)  # (2k, 2k, 8) uint32 words
    digest_bytes = words_to_bytes(dig)  # (2k, 2k, 32)
    leaf_ns = _leaf_namespaces(q0_ns, k)
    row_roots, col_roots = _digest_grid_roots(digest_bytes, leaf_ns)
    return eds, row_roots, col_roots


def _roots_of(shares: jnp.ndarray, m2: jnp.ndarray,
              fused: bool | None = None, xor: bool | None = None):
    """Shared core: (k,k,512) -> (eds, row_roots, col_roots).

    fused=None resolves via _fused_active (Pallas kernels on an
    accelerator backend, XLA spelling otherwise); xor=None via
    _xor_active (measured-table contraction choice, ADR-024); True/False
    pin a spelling for A/B benching. Byte-identical any way (pinned by
    tests/test_fused_roots.py, tests/test_xor_schedule.py)."""
    k = shares.shape[0]
    if fused is None:
        fused = _fused_active(k)
    if xor is None:
        xor = _xor_active(k)
    if fused:
        return _roots_of_fused(shares, m2, xor=xor)
    if xor:
        from celestia_tpu.ops import xor_schedule

        eds = xor_schedule.extend_square_xor(
            shares, xor_schedule.compile_schedule(k)
        )
    else:
        eds = rs_tpu.extend_square(shares, m2)
    leaf_ns = _leaf_namespaces(shares[..., :NAMESPACE_SIZE], k)
    row_roots, col_roots = nmt_roots_of_eds(eds, leaf_ns)
    return eds, row_roots, col_roots


def extend_and_root(
    shares: jnp.ndarray, m2: jnp.ndarray, fused: bool | None = None
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(k, k, 512) uint8 -> (eds (2k,2k,512), row_roots (2k,90),
    col_roots (2k,90), dah_hash (32,)). m2 = rs_tpu.encode_bit_matrix(k);
    fused as in _roots_of."""
    eds, row_roots, col_roots = _roots_of(shares, m2, fused=fused)
    dah = merkle_root_pow2(jnp.concatenate([row_roots, col_roots], axis=0))
    return eds, row_roots, col_roots, dah


def extend_and_roots_only(shares: jnp.ndarray, m2: jnp.ndarray):
    """Deployment variant: (k,k,512) -> (eds, row_roots, col_roots).

    The DAH hash over the 4k axis roots is a tiny (~1k-node) merkle tree —
    latency-bound on device but ~sub-ms on host, and the node needs the
    roots host-side anyway to build the DataAvailabilityHeader. So the
    device program stops at the axis roots and the host finishes the DAH
    (byte-identical; see app/_extend_and_hash)."""
    return _roots_of(shares, m2)


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("extend.for_k")
def _jitted_for_k(k: int):
    m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))

    @jax.jit
    def run(shares):
        return extend_and_root(shares, m2)

    return run


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("extend.roots_for_k")
def _jitted_roots_for_k(k: int):
    m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))

    @jax.jit
    def run(shares):
        return extend_and_roots_only(shares, m2)

    return run


def _profile_fence(out, entry: str, dispatch_start: float,
                   **attrs) -> None:
    """Fenced device-time profiling (ADR-022, opt-in): when this
    dispatch is profile-sampled, block until the result is ready and
    emit a ``profile.fence`` span covering dispatch→ready — the REAL
    device completion time the async dispatch queue hides from wall
    spans. Off by default (``tracing.enable_profiling``): a fence
    serializes the device stream, which costs exactly the
    dispatch/fetch overlap the resident paths exist to keep."""
    if not tracing.profile_sample():
        return
    try:
        jax.block_until_ready(out)
        tracing.emit("profile.fence", dispatch_start, entry=entry,
                     fenced=True, **attrs)
    except Exception:  # noqa: BLE001
        pass


# ------------------------------------------------------------------ #
# Production mesh routing (specs/parallel.md §Production routing): when
# an operator configures a device mesh (parallel.configure_mesh), the
# roots/levels host entries below route through the explicit-collective
# row-sharded spelling in celestia_tpu/parallel. Row-block sharding
# matches the NMT tree, so the sharded outputs are byte-identical to
# the single-device programs — flipping the mesh on is purely a
# placement decision. The state lives HERE because parallel imports
# this module at import time; the sharded builders are fetched lazily
# inside the jit caches to keep the import graph acyclic.

_ACTIVE_MESH = None


def set_active_mesh(mesh) -> None:
    """Install (None clears) the process-wide mesh. Public entry:
    parallel.configure_mesh. Drops the sharded jit caches — their
    compiled programs bake in the mesh they were traced under."""
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    _jitted_rowsharded.cache_clear()
    _jitted_rowsharded_roots.cache_clear()
    _jitted_rowsharded_levels.cache_clear()
    _jitted_rowsharded_full.cache_clear()


def active_mesh():
    return _ACTIVE_MESH


def _mesh_if_divisible(n_rows: int):
    """The active mesh when the row-sharded spelling can place n_rows
    rows on its 'sp' axis (exact division), else None — the caller
    falls back to the single-device program, so a k that does not
    divide the mesh degrades instead of erroring."""
    m = _ACTIVE_MESH
    if m is None or n_rows % m.shape["sp"]:
        return None
    return m


def _mesh_compile_key():
    """The mesh component of the sharded builders' compile key: a mesh
    flip retraces even at the same k (the compiled program bakes the
    mesh in — set_active_mesh clears the jit caches for the same
    reason)."""
    m = _ACTIVE_MESH
    return None if m is None else tuple(sorted(m.shape.items()))


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("extend.rowsharded",
                              key_extra=_mesh_compile_key)
def _jitted_rowsharded(k: int):
    from celestia_tpu import parallel

    return parallel.extend_and_root_rowsharded(_ACTIVE_MESH, k)


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("extend.rowsharded_roots",
                              key_extra=_mesh_compile_key)
def _jitted_rowsharded_roots(k: int):
    """Roots-only sharded spelling: the EDS stays out of the jit
    outputs (XLA drops the dead reassembly), matching roots_device's
    no-EDS-materialization contract on the mesh path."""
    from celestia_tpu import parallel

    inner = parallel.extend_and_root_rowsharded(_ACTIVE_MESH, k)
    return jax.jit(lambda s: inner(s)[1:])


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("extend.rowsharded_levels",
                              key_extra=_mesh_compile_key)
def _jitted_rowsharded_levels(k: int):
    from celestia_tpu import parallel

    return parallel.eds_row_levels_rowsharded(_ACTIVE_MESH, k)


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("extend.rowsharded_full",
                              key_extra=_mesh_compile_key)
def _jitted_rowsharded_full(k: int):
    from celestia_tpu import parallel

    return parallel.extend_root_levels_rowsharded(_ACTIVE_MESH, k)


def _stage_sharded(arr, mesh):
    """H2D-stage a row-sharded operand: each row block lands directly
    on its 'sp' shard instead of one device plus an in-program reshard.
    Host arrays ride the telemetered transfer path; device-resident
    inputs (levels over an extend output) reshard without a host
    round-trip."""
    if isinstance(arr, np.ndarray):
        from celestia_tpu.ops import transfers

        return transfers.device_put_sharded_rows(arr, mesh,
                                                 site="extend.stage")
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(
        arr, NamedSharding(mesh, PartitionSpec("sp", None, None))
    )


def extend_and_root_staged(dev):
    """Device-in, device-out extend for the block pipeline
    (node/pipeline.py): operands are already staged (possibly
    mesh-sharded) and outputs stay device arrays so consecutive blocks
    overlap on the async dispatch queue. Routed through the row-sharded
    spelling when a mesh is active. Returns (eds, rows, cols, dah)."""
    k = int(dev.shape[0])
    mesh = _mesh_if_divisible(k)
    if mesh is not None:
        return _jitted_rowsharded(k)(dev)
    return _jitted_for_k(k)(dev)


def extend_root_levels_staged(dev):
    """Device-in, device-out extend + roots + EVERY row-tree level for
    the block pipeline's compute leg. On the mesh path this is ONE
    sharded dispatch per block — the fused spelling hashes each NMT leaf
    digest once and derives the level stack from the same leaf tensors
    the root reductions consume (parallel.extend_root_levels_rowsharded)
    — where the unfused pair (extend_and_root_staged +
    eds_row_levels_device) pays two dispatches and a second full leaf
    SHA pass. Falls back to the unfused single-device jits when no mesh
    divides k. Returns (eds, rows, cols, dah, levels_tuple), all device
    arrays, byte-identical to the unfused pair either way."""
    k = int(dev.shape[0])
    mesh = _mesh_if_divisible(k)
    if mesh is not None:
        return _jitted_rowsharded_full(k)(dev)
    eds, rows, cols, dah = _jitted_for_k(k)(dev)
    return eds, rows, cols, dah, tuple(_jitted_row_levels(k)(eds))


def extend_roots_device(shares: np.ndarray):
    """Host deployment entry: (k,k,512) uint8 -> numpy (eds, row_roots,
    col_roots); the caller computes the DAH hash host-side (da module)."""
    k = int(shares.shape[0])
    mesh = _mesh_if_divisible(k)
    with tracing.span("extend.device", backend="tpu", k=k,
                      entry="extend_roots_device"):
        faults.fire("device.extend", entry="extend_roots_device")
        with tracing.span("extend.stage", backend="tpu", k=k):
            dev = (_stage_sharded(shares, mesh) if mesh is not None
                   else jnp.asarray(shares))
        # RS extend + NMT reduction are ONE fused XLA program; the span
        # covers dispatch through the host fetch of all three outputs
        with tracing.span("extend.rs_nmt", backend="tpu", k=k,
                          fused="rs+nmt", sharded=mesh is not None):
            t0 = time.perf_counter()
            if mesh is not None:
                eds, rows, cols, _dah = _jitted_rowsharded(k)(dev)
            else:
                eds, rows, cols = _jitted_roots_for_k(k)(dev)
            _profile_fence(cols, "extend_roots_device", t0, k=k)
        # SDC model: the result tensor is damaged in flight (HBM upset,
        # bad D2H) — the audit below must catch what the flip injects
        flip = faults.fire("device.extend.output",
                           entry="extend_roots_device")
        if flip is not None:
            eds = jnp.asarray(flip(eds))
        eng = integrity.get()
        if eng.enabled:
            integrity.audit_or_raise(eng, eds, k,
                                     site="device.extend.output",
                                     where="device.extend")
        return np.asarray(eds), np.asarray(rows), np.asarray(cols)


def extend_roots_device_resident(shares: np.ndarray):
    """(k,k,512) uint8 -> (eds_device, rows_np, cols_np).

    The EDS stays a DEVICE buffer — only the tiny axis roots (2·2k·90
    bytes) cross back to host. The node's ExtendBlock path wraps the
    handle in a lazy ExtendedDataSquare and fetches bytes only if the
    block store actually serves shares; the repair path consumes the
    handle directly (ops/repair_tpu.stage_resident_repair) with no
    host round-trip. ref: app/extend_block.go:14."""
    k = int(shares.shape[0])
    mesh = _mesh_if_divisible(k)
    with tracing.span("extend.device", backend="tpu", k=k,
                      entry="extend_roots_device_resident"):
        faults.fire("device.extend", entry="extend_roots_device_resident")
        with tracing.span("extend.stage", backend="tpu", k=k):
            dev = (_stage_sharded(shares, mesh) if mesh is not None
                   else jnp.asarray(shares))
        with tracing.span("extend.rs_nmt", backend="tpu", k=k,
                          fused="rs+nmt", sharded=mesh is not None):
            t0 = time.perf_counter()
            if mesh is not None:
                eds, rows, cols, _dah = _jitted_rowsharded(k)(dev)
            else:
                eds, rows, cols = _jitted_roots_for_k(k)(dev)
            _profile_fence(cols, "extend_roots_device_resident", t0, k=k)
        flip = faults.fire("device.extend.output",
                           entry="extend_roots_device_resident")
        if flip is not None:
            eds = jnp.asarray(flip(eds))
        eng = integrity.get()
        if eng.enabled:
            integrity.audit_or_raise(eng, eds, k,
                                     site="device.extend.output",
                                     where="device.extend")
        return eds, np.asarray(rows), np.asarray(cols)


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("extend.eds_roots")
def _jitted_eds_roots(k: int):
    @jax.jit
    def run(eds):
        leaf_ns = _leaf_namespaces(eds[:k, :k, :NAMESPACE_SIZE], k)
        return nmt_roots_of_eds(eds, leaf_ns)

    return run


def eds_roots_device(eds):
    """NMT axis roots of an EXISTING (2k,2k,512) EDS (host or device
    array) -> numpy (row_roots, col_roots). Leaf namespaces are read
    from Q0 on device, so a device-resident EDS (repair output, extend
    handle) is verified without fetching a single share byte."""
    k = int(eds.shape[0]) // 2
    with tracing.span("extend.nmt", backend="tpu", k=k,
                      entry="eds_roots_device"):
        t0 = time.perf_counter()
        rows, cols = _jitted_eds_roots(k)(jnp.asarray(eds))
        _profile_fence(cols, "eds_roots_device", t0, k=k)
        return np.asarray(rows), np.asarray(cols)


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("extend.row_levels")
def _jitted_row_levels(k: int):
    @jax.jit
    def run(eds):
        leaf_ns = _leaf_namespaces(eds[:k, :k, :NAMESPACE_SIZE], k)
        leaf_nodes = nmt_leaf_nodes(leaf_ns, eds)  # (2k, 2k, 90)
        return nmt_reduce_levels(leaf_nodes)

    return run


def eds_row_levels_device(eds) -> list[np.ndarray]:
    """EVERY row-tree level of an existing (2k,2k,512) EDS, hashed once
    on device: [leaf nodes (2k, 2k, 90), (2k, k, 90), ..., roots
    (2k, 1, 90)] as numpy. levels[L][r, j] is row r's subtree node
    covering leaves [j·2^L, (j+1)·2^L) — exactly the memo
    proof.NmtRowProver builds by hashing on host, so
    NmtRowProver.from_node_levels can serve byte-identical range proofs
    with ZERO host hashing (ADR-019; the 'device-side proof hashing'
    depth PR 7 left open). ~2·(2k)²·90 B crosses the interconnect —
    3 MB at k=64 — instead of the host paying O(w²) SHA per height."""
    k = int(eds.shape[0]) // 2
    mesh = _mesh_if_divisible(2 * k)  # sp shards the 2k EDS rows here
    with tracing.span("extend.nmt_levels", backend="tpu", k=k,
                      entry="eds_row_levels_device",
                      sharded=mesh is not None):
        t0 = time.perf_counter()
        if mesh is not None:
            dev = _stage_sharded(eds, mesh)
            levels = _jitted_rowsharded_levels(k)(dev)
        else:
            levels = _jitted_row_levels(k)(jnp.asarray(eds))
        _profile_fence(levels[-1], "eds_row_levels_device", t0, k=k)
        return [np.asarray(lv) for lv in levels]


def fused_roots_reference(shares: np.ndarray, tile: int | None = None,
                          xor: bool = False):
    """Eager CPU spelling of the FUSED pipeline for parity tests:
    (k,k,512) -> numpy (eds, row_roots, col_roots), running
    rs_pallas's *_reference tile math (the kernels' exact bodies,
    executed eagerly — see ops/sha256_pallas.sha256_words on why
    interpret-mode jit is unusable for the unrolled SHA graph on CPU)
    plus the same digest-grid NMT reduce the device program runs.
    `tile` (rs_pallas reference tile override) trades eager dispatch
    count for op width — byte-identical output either way. xor=True
    runs the XOR-schedule contraction's reference spelling instead of
    the dense one (ADR-024), mirroring _roots_of_fused's switch."""
    from celestia_tpu.ops import rs_pallas

    if xor:
        from celestia_tpu.ops import xor_schedule

        def _enc_ref(x, _m2, t):
            return xor_schedule.encode2d_xor_hash_reference(x, t)
    else:
        _enc_ref = rs_pallas.encode2d_hash_reference

    k = int(shares.shape[0])
    n = k * SHARE_SIZE
    m2 = rs_tpu.encode_bit_matrix(k)
    x0 = np.asarray(shares, dtype=np.uint8).reshape(k, n)
    q0_ns = np.asarray(shares)[..., :NAMESPACE_SIZE]
    ns_pad = np.asarray(rs_pallas.pad_namespaces(jnp.asarray(q0_ns)))
    d0 = rs_pallas.leaf_digests2d_reference(x0, ns_pad, tile)
    q2f, d2 = _enc_ref(x0, m2, tile)
    q2 = q2f.reshape(k, k, SHARE_SIZE)
    x0t = np.swapaxes(shares, 0, 1).reshape(k, n)
    q1t, d1t = _enc_ref(x0t, m2, tile)
    q1 = np.swapaxes(q1t.reshape(k, k, SHARE_SIZE), 0, 1)
    q2t = np.swapaxes(q2, 0, 1).reshape(k, n)
    q3t, d3t = _enc_ref(q2t, m2, tile)
    q3 = np.swapaxes(q3t.reshape(k, k, SHARE_SIZE), 0, 1)
    eds = np.concatenate([
        np.concatenate([np.asarray(shares), q1], axis=1),
        np.concatenate([q2, q3], axis=1),
    ], axis=0)
    dig = np.concatenate([
        np.concatenate([d0, np.swapaxes(d1t, 0, 1)], axis=1),
        np.concatenate([d2, np.swapaxes(d3t, 0, 1)], axis=1),
    ], axis=0)
    digest_bytes = np.asarray(words_to_bytes(jnp.asarray(dig)))
    leaf_ns = np.asarray(_leaf_namespaces(jnp.asarray(q0_ns), k))
    # cached builder, not a fresh jax.jit per call: the old spelling
    # re-traced the digest-grid reduce on EVERY reference run — exactly
    # the recompile-per-call pattern the devledger watchdog flags
    rows, cols = _jitted_digest_grid_roots()(
        jnp.asarray(digest_bytes), jnp.asarray(leaf_ns)
    )
    return eds, np.asarray(rows), np.asarray(cols)


@functools.lru_cache(maxsize=1)
@devledger.instrument_builder("extend.digest_grid_roots")
def _jitted_digest_grid_roots():
    return jax.jit(_digest_grid_roots)


# ------------------------------------------------------------------ #
# Device-side square assembly from the resident blob arena
# (ops/blob_pool.py). The proposal path's wall time is otherwise
# dominated by uploading the 8 MB square; with the blob bytes already
# in HBM, only share metadata (a few hundred KB) crosses per proposal
# and the assembled square feeds the fused extend+NMT pipeline without
# ever existing host-side.


def _derive_cells(blob_meta, host_sparse, k: int):
    """Expand PER-BLOB metadata into the per-cell vectors ON DEVICE.

    blob_meta is (4, B) int32 — [start_cell | n_shares | arena_off |
    blob_len] with starts ascending (the builder lays blobs out at an
    increasing cursor) and padding rows start_cell = S, n_shares = 0.
    host_sparse is (2, Hc) int32 — [cell_pos | host_row] pairs for the
    cells NOT covered by a resident blob, padding pos = S (dropped).

    Deriving here is what shrinks the proposal upload from O(k²)
    per-cell vectors (~320 KB at k=128) to O(#blobs + #host cells)
    rows (~1-10 KB): on a high-RTT, low-bandwidth link the metadata
    transfer WAS the assembled path's wall time."""
    s = k * k
    s_idx = jnp.arange(s, dtype=jnp.int32)
    starts = blob_meta[0]
    b = jnp.clip(
        jnp.searchsorted(starts, s_idx, side="right").astype(jnp.int32) - 1,
        0, blob_meta.shape[1] - 1,
    )
    j_in = s_idx - starts[b]
    in_blob = (j_in >= 0) & (j_in < blob_meta[1][b])
    first = FIRST_SPARSE
    cont = CONT_SPARSE
    cell_first = in_blob & (j_in == 0)
    doff = jnp.where(cell_first, 0, first + (j_in - 1) * cont)
    data_start = jnp.where(in_blob, blob_meta[2][b] + doff, 0)
    cap = jnp.where(cell_first, first, cont)
    data_len = jnp.where(
        in_blob, jnp.minimum(cap, blob_meta[3][b] - doff), 0
    )
    cell_blob = jnp.where(in_blob, b, 0)
    cell_host_row = (
        jnp.full((s,), -1, jnp.int32)
        .at[host_sparse[0]]
        .set(host_sparse[1], mode="drop")
    )
    return cell_host_row, cell_blob, cell_first, data_start, data_len


def _assemble_square(arena, host_shares, blob_meta, host_sparse,
                     ns_len_table, k: int):
    """Build the (k,k,512) share square on device.

    Inputs per proposal: the resident arena, the dedup'd host-share
    table, ONE (4, B) per-blob int32 block, ONE (2, Hc) sparse
    host-cell block, and ONE (B, 33) uint8 block (29-byte namespace ‖
    4-byte BE blob length). The per-cell vectors are DERIVED on device
    (_derive_cells) — only per-blob/host-cell rows cross the
    interconnect, which matters on a high-RTT link where both latency
    and bandwidth are paid per proposal.

    Each cell is either a host-table share (host_row >= 0) or a sparse
    blob share assembled in place: namespace ‖ info ‖ [seq len] ‖
    arena[data_start : data_start+data_len] ‖ zeros — exactly the
    sparse splitter's layout (shares/splitters.py write), so the result
    is byte-identical to the host-built square (pinned by tests)."""
    j = jnp.arange(SHARE_SIZE, dtype=jnp.int32)  # (512,)
    cell_host_row, cell_blob, cell_first, data_start, data_len = \
        _derive_cells(blob_meta, host_sparse, k)

    blob_idx = jnp.clip(cell_blob, 0, ns_len_table.shape[0] - 1)
    ns = ns_len_table[blob_idx, :NAMESPACE_SIZE]  # (S, 29)
    info = jnp.where(cell_first, 1, 0).astype(jnp.uint8)  # share version 0
    seq_bytes = ns_len_table[blob_idx, NAMESPACE_SIZE:]  # (S, 4) BE length
    prefix = jnp.concatenate([ns, info[:, None], seq_bytes], axis=-1)  # (S, 34)
    prefix_len = jnp.where(cell_first, 34, 30).astype(jnp.int32)

    pref_padded = jnp.pad(prefix, ((0, 0), (0, SHARE_SIZE - prefix.shape[1])))
    data_pos = j[None, :] - prefix_len[:, None]  # (S, 512)
    arena_idx = jnp.clip(
        data_start[:, None] + data_pos, 0, arena.shape[0] - 1
    )
    arena_vals = arena[arena_idx]  # (S, 512) HBM gather
    in_prefix = j[None, :] < prefix_len[:, None]
    in_data = (~in_prefix) & (data_pos < data_len[:, None])
    blob_cells = jnp.where(
        in_prefix, pref_padded, jnp.where(in_data, arena_vals, 0)
    )

    hrow = jnp.clip(cell_host_row, 0, host_shares.shape[0] - 1)
    host_cells = host_shares[hrow]
    cells = jnp.where(
        (cell_host_row >= 0)[:, None], host_cells, blob_cells
    )
    return cells.reshape(k, k, SHARE_SIZE)


@functools.lru_cache(maxsize=16)
@devledger.instrument_builder("extend.assembled_roots")
def _jitted_assembled_roots(k: int, h_pad: int, b_pad: int, hc_pad: int,
                            n_arena: int):
    m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))

    @jax.jit
    def run(arena, host_shares, blob_meta, host_sparse, ns_len_table):
        square = _assemble_square(arena, host_shares, blob_meta,
                                  host_sparse, ns_len_table, k)
        return _rows_cols_only(square, m2)

    return run


def _pow2_at_least(n: int, floor: int) -> int:
    p = floor
    while p < n:
        p <<= 1
    return p


def assembled_roots(
    arena,
    host_shares: np.ndarray,    # (H, 512) uint8 — dedup'd host table
    host_pos: np.ndarray,       # (Hc,) int32 — cell indexes of host cells
    host_row: np.ndarray,       # (Hc,) int32 — row into host_shares
    blob_start: np.ndarray,     # (B,) int32 — first cell per resident blob, ASCENDING
    blob_nshares: np.ndarray,   # (B,) int32
    blob_off: np.ndarray,       # (B,) int32 — absolute arena offsets
    blob_len: np.ndarray,       # (B,) int32 — blob byte lengths
    ns_table: np.ndarray,       # (B, 29) uint8
    k: int,
):
    """Host entry: assemble the square ON DEVICE from the blob arena and
    return numpy (row_roots, col_roots) — the roots-only proposal path.
    The upload is O(#blobs + #host cells), NOT O(k²): the per-cell
    vectors are derived on device (_derive_cells). Pad counts are
    rounded to powers of two so the jit cache stays small."""
    s = k * k
    starts_arr = np.asarray(blob_start, np.int64)
    if len(starts_arr) > 1 and not np.all(np.diff(starts_arr) > 0):
        # the device searchsorted derivation silently misattributes
        # cells if starts are not strictly ascending — fail LOUDLY here
        # rather than sign a proposal with corrupt roots
        raise ValueError("blob_start must be strictly ascending")
    with tracing.span("extend.assemble", backend="tpu", k=k,
                      blobs=len(ns_table), host_cells=len(host_pos)):
        return _assembled_roots_traced(
            arena, host_shares, host_pos, host_row, blob_start,
            blob_nshares, blob_off, blob_len, ns_table, k, s)


def _assembled_roots_traced(arena, host_shares, host_pos, host_row,
                            blob_start, blob_nshares, blob_off, blob_len,
                            ns_table, k, s):
    h_pad = _pow2_at_least(max(len(host_shares), 1), 16)
    b_pad = _pow2_at_least(max(len(ns_table), 1), 8)
    hc_pad = _pow2_at_least(max(len(host_pos), 1), 16)
    from celestia_tpu.ops import transfers

    # Each metadata block is DISPATCHED (async device_put) as soon as it
    # is built, so its DMA streams while the host packs the next block —
    # and the staging traffic shows up in the transfer telemetry
    # (site=proposal.stage), making "tens of KB instead of MB" auditable
    # on /metrics rather than folklore.
    stage = lambda a: transfers.device_put_chunked(  # noqa: E731
        a, site="proposal.stage"
    )
    hs = np.zeros((h_pad, SHARE_SIZE), np.uint8)
    if len(host_shares):
        hs[: len(host_shares)] = host_shares
    hs_dev = stage(hs)
    nslen = np.zeros((b_pad, NAMESPACE_SIZE + 4), np.uint8)
    if len(ns_table):
        nslen[: len(ns_table), :NAMESPACE_SIZE] = ns_table
        bl = np.asarray(blob_len, dtype=">u4")
        nslen[: len(ns_table), NAMESPACE_SIZE:] = bl.view(np.uint8).reshape(
            len(ns_table), 4
        )
    nslen_dev = stage(nslen)
    # padding rows: start = S (past every cell, keeps starts sorted so
    # searchsorted never lands a real cell there), n_shares = 0
    bm = np.zeros((4, b_pad), np.int32)
    bm[0, :] = s
    n_b = len(ns_table)
    if n_b:
        bm[0, :n_b] = np.asarray(blob_start, np.int32)
        bm[1, :n_b] = np.asarray(blob_nshares, np.int32)
        bm[2, :n_b] = np.asarray(blob_off, np.int32)
        bm[3, :n_b] = np.asarray(blob_len, np.int32)
    bm_dev = stage(bm)
    hsp = np.full((2, hc_pad), s, np.int32)  # pos = S → scatter-dropped
    n_h = len(host_pos)
    if n_h:
        hsp[0, :n_h] = np.asarray(host_pos, np.int32)
        hsp[1, :n_h] = np.asarray(host_row, np.int32)
    hsp_dev = stage(hsp)
    fn = _jitted_assembled_roots(k, h_pad, b_pad, hc_pad,
                                 int(arena.shape[0]))
    rows, cols = fn(arena, hs_dev, bm_dev, hsp_dev, nslen_dev)
    return np.asarray(rows), np.asarray(cols)


def extend_and_root_batched(shares: jnp.ndarray, m2: jnp.ndarray,
                            fused: bool | None = None):
    """(B, k, k, 512) -> batched (eds, row_roots, col_roots, dah).

    The multi-block form: a node that is catching up (state sync / block
    replay) or serving many proposals extends B squares at once; B is the
    data-parallel axis when sharded over a mesh (see __graft_entry__).
    fused as in _roots_of.
    """
    return jax.vmap(lambda s: extend_and_root(s, m2, fused))(shares)


def _rows_cols_only(shares: jnp.ndarray, m2: jnp.ndarray,
                    fused: bool | None = None, xor: bool | None = None):
    """The ONE roots-only core: (k,k,512) -> (row_roots, col_roots)
    with no EDS in the outputs — the EDS stays an XLA intermediate.
    Every roots-only spelling (single, batched, their jit caches)
    derives from this function so root computation cannot diverge
    between the replay verifier and the proposer path."""
    _eds, rows, cols = _roots_of(shares, m2, fused=fused, xor=xor)
    return rows, cols


def _batch_chunk(k: int, b: int) -> int:
    """Concurrency width for a batched roots dispatch.

    Small squares vmap the whole batch (dispatch amortization wins);
    large squares bound the HBM working set — a k=128 square's fused
    extend+hash intermediates already saturate HBM bandwidth, so
    lanes-across-the-whole-batch buys nothing and the B× working set
    evicts everything (bench 7b round 3: vmapped k=128 = 7.99 ms/square
    vs 5.03 single). The large-k cap is 2, not 1: pairing squares keeps
    the working set bounded at 2× a single square while doubling the
    lanes through the latency-bound NMT tree-top levels and halving the
    dispatch count — the vmappable middle ground between the regressing
    full vmap and the round-5 "pipelined-singles" fallback (bench 7b
    reports the spelling in use; the perf ledger gates the wall).
    Returns the largest divisor of b not exceeding the per-size cap so
    the group reshape is exact."""
    cap = b if k <= 64 else 2
    chunk = min(cap, b)
    while b % chunk:
        chunk -= 1
    return chunk


def roots_only_batched(shares: jnp.ndarray, m2: jnp.ndarray, chunk: int | None = None):
    """(B, k, k, 512) -> batched (row_roots, col_roots) — NO EDS output.

    The replay/state-sync verifier only compares DAH roots, and keeping
    B full EDS buffers (B × 32 MB at k=128) out of the program's outputs
    lets XLA treat the extended square as a consumable intermediate
    instead of allocating and writing every byte of it to HBM.

    The batch rides lax.map over vmapped chunks of _batch_chunk(k, B)
    squares: one dispatch regardless of size, with the HBM working set
    bounded at chunk× a single square's — this is what makes k=128
    batching match the single-dispatch ms/square instead of regressing.
    """
    b = shares.shape[0]
    if chunk is None:
        chunk = _batch_chunk(shares.shape[1], b)
    if chunk >= b:
        return jax.vmap(lambda s: _rows_cols_only(s, m2))(shares)
    groups = shares.reshape(b // chunk, chunk, *shares.shape[1:])
    rows, cols = jax.lax.map(
        lambda g: jax.vmap(lambda s: _rows_cols_only(s, m2))(g), groups
    )
    return (
        rows.reshape(b, *rows.shape[2:]),
        cols.reshape(b, *cols.shape[2:]),
    )


@functools.lru_cache(maxsize=8)
@devledger.instrument_builder("extend.batched_roots")
def _jitted_batched_roots(k: int):
    m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))
    return jax.jit(lambda shares: roots_only_batched(shares, m2))


@functools.lru_cache(maxsize=16)
@devledger.instrument_builder("extend.chunk_roots")
def _jitted_chunk_roots(k: int, chunk: int):
    """vmapped roots over a FIXED chunk of squares — the unit the
    large-k pipelined dispatch queues (see batched_roots_device)."""
    m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))
    return jax.jit(jax.vmap(lambda s: _rows_cols_only(s, m2)))


@functools.lru_cache(maxsize=16)
@devledger.instrument_builder("extend.roots_noeds")
def _jitted_roots_noeds(k: int, fused: bool | None = None,
                        xor: bool | None = None):
    """fused=None / xor=None (the defaults every production caller
    uses) freeze the _fused_active / _xor_active decisions into this
    cache entry at first trace; True/False build explicitly-pinned
    spellings for A/B benching (bench.py --fused-kernels,
    --xor-schedule)."""
    m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))
    return jax.jit(
        lambda shares: _rows_cols_only(shares, m2, fused=fused, xor=xor)
    )


def roots_device(shares: np.ndarray):
    """Host entry: (k,k,512) uint8 -> numpy (row_roots, col_roots),
    jit-cached, EDS never materialized as an output."""
    k = int(shares.shape[0])
    mesh = _mesh_if_divisible(k)
    with tracing.span("extend.device", backend="tpu", k=k,
                      entry="roots_device"):
        faults.fire("device.extend", entry="roots_device")
        with tracing.span("extend.stage", backend="tpu", k=k):
            dev = (_stage_sharded(shares, mesh) if mesh is not None
                   else jnp.asarray(shares))
        with tracing.span("extend.rs_nmt", backend="tpu", k=k,
                          fused="rs+nmt", sharded=mesh is not None):
            t0 = time.perf_counter()
            if mesh is not None:
                rows, cols, _dah = _jitted_rowsharded_roots(k)(dev)
            else:
                rows, cols = _jitted_roots_noeds(k)(dev)
            _profile_fence(cols, "roots_device", t0, k=k)
            return np.asarray(rows), np.asarray(cols)


def batched_roots_device(shares):
    """Host entry for the replay verifier: B squares of (k,k,512) uint8
    (a list, or a stacked (B,k,k,512) array) -> numpy
    (row_roots, col_roots), jit-cached per square size.

    Small squares ride ONE vmapped dispatch (amortizes dispatch
    overhead); large squares dispatch vmapped CHUNKS of
    _batch_chunk(k, b) squares through an async-pipelined queue — the
    working set stays bounded at chunk× a single square's (the full-vmap
    k=128 spelling paid HBM-working-set and gather overheads, bench 7b
    round 3) while the dispatch count drops chunk-fold vs the old
    per-square queue. Accepting a list means the large-k branch never
    builds the contiguous B×8 MB stacked copy — only chunk squares are
    stacked at a time. Every branch is the same `_rows_cols_only` core,
    so results cannot diverge."""
    b = len(shares)
    k = int(shares[0].shape[0])
    with tracing.span("extend.device", backend="tpu", k=k, batch=b,
                      entry="batched_roots_device"):
        chunk = _batch_chunk(k, b)
        if chunk >= b:
            stacked = shares if isinstance(shares, np.ndarray) else np.stack(shares)
            t0 = time.perf_counter()
            rows, cols = _jitted_batched_roots(k)(jnp.asarray(stacked))
            _profile_fence(cols, "batched_roots_device", t0, k=k, batch=b)
            return np.asarray(rows), np.asarray(cols)
        if chunk > 1:
            fn = _jitted_chunk_roots(k, chunk)
            full = b - b % chunk
            outs = [
                fn(jnp.asarray(np.stack([
                    np.asarray(shares[g + j]) for j in range(chunk)
                ])))
                for g in range(0, full, chunk)
            ]  # async queue of vmapped chunks
            rows = [np.asarray(r) for r, _c in outs]
            cols = [np.asarray(c) for _r, c in outs]
            if full < b:
                # ragged tail rides the single-square program (already
                # jit-cached) rather than compiling a one-off chunk shape
                single = _jitted_roots_noeds(k)
                rest = [single(jnp.asarray(shares[i])) for i in range(full, b)]
                rows.append(np.stack([np.asarray(r) for r, _c in rest]))
                cols.append(np.stack([np.asarray(c) for _r, c in rest]))
            return np.concatenate(rows), np.concatenate(cols)
        fn = _jitted_roots_noeds(k)
        outs = [fn(jnp.asarray(shares[i])) for i in range(b)]  # async queue
        return (
            np.stack([np.asarray(r) for r, _c in outs]),
            np.stack([np.asarray(c) for _r, c in outs]),
        )


def extend_and_root_device(shares: np.ndarray):
    """Host entry: (k,k,512) uint8 numpy -> numpy (eds, row_roots, col_roots, dah)."""
    k = int(shares.shape[0])
    mesh = _mesh_if_divisible(k)
    with tracing.span("extend.device", backend="tpu", k=k,
                      entry="extend_and_root_device"):
        faults.fire("device.extend", entry="extend_and_root_device")
        with tracing.span("extend.stage", backend="tpu", k=k):
            dev = (_stage_sharded(shares, mesh) if mesh is not None
                   else jnp.asarray(shares))
        with tracing.span("extend.rs_nmt", backend="tpu", k=k,
                          fused="rs+nmt+dah", sharded=mesh is not None):
            t0 = time.perf_counter()
            if mesh is not None:
                eds, rows, cols, dah = _jitted_rowsharded(k)(dev)
            else:
                eds, rows, cols, dah = _jitted_for_k(k)(dev)
            _profile_fence(dah, "extend_and_root_device", t0, k=k)
            return (np.asarray(eds), np.asarray(rows), np.asarray(cols),
                    np.asarray(dah))
