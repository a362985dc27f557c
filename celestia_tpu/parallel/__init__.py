"""Multi-chip parallelism: sharded ExtendBlock over a device mesh.

The reference scales per-axis work across goroutines (SURVEY §2.5:
rsmt2d encodes rows/columns in parallel; NMTs per axis). The TPU-native
scaling axes are:

- dp (data parallel): independent squares (blocks) across devices — block
  replay, proposal bursts, catching-up nodes.
- sp (sequence parallel analogue): rows of one square across devices
  (SURVEY §5: "square size is the sequence axis"); row extension and row
  NMTs are local, column extension is a contraction over the sharded row
  axis and becomes a psum over ICI, and column NMT reduction all-gathers
  the (small) leaf-digest tensor.

Two implementations:
- `sharded_extend_and_root` — jit + NamedSharding annotations; XLA chooses
  the collectives (the recommended default).
- `extend_and_root_rowsharded` — shard_map with *explicit* collectives
  (psum for the GF(2) column contraction, all_gather for the column
  trees), the hand-written spelling of the same program for when the
  schedule must be pinned.

GF(2) note: partial products of the bit-matmul are integer counts;
summing counts across devices then reducing mod 2 is exactly the XOR of
the per-device partial parities, so the cross-device combine is a plain
psum in int32 followed by `& 1`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from celestia_tpu.ops import rs_tpu
from celestia_tpu.ops.extend_tpu import (
    extend_and_root,
    extend_and_root_batched,
)


def make_mesh(dp: int, sp: int, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if len(devices) < dp * sp:
        raise ValueError(f"need {dp * sp} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[: dp * sp]).reshape(dp, sp), ("dp", "sp"))


def configure_mesh(mesh: Mesh | None) -> None:
    """Install (or clear, with None) the PROCESS-WIDE active mesh.

    While a mesh is configured, ops/extend_tpu.py's roots/levels host
    entries route through the explicit-collective row-sharded spelling
    below whenever the square's row count divides the mesh's 'sp' axis —
    byte-identical outputs either way (specs/parallel.md §Production
    routing), so flipping the mesh on is purely a placement decision.
    The state lives in extend_tpu (parallel imports extend_tpu, not the
    reverse); this is the operator-facing switch."""
    from celestia_tpu.ops import extend_tpu

    if mesh is not None and "sp" not in mesh.shape:
        raise ValueError("mesh must carry an 'sp' axis (see make_mesh)")
    extend_tpu.set_active_mesh(mesh)


def sharded_extend_and_root(mesh: Mesh, k: int):
    """Compiled batched extend+root with (dp, sp) input sharding; XLA
    inserts the collectives implied by the shardings. XLA cannot
    partition a Mosaic kernel, so this spelling pins the XLA extend
    (fused=False) on every backend."""
    m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))
    in_sharding = NamedSharding(mesh, P("dp", "sp", None, None))
    return jax.jit(
        lambda s: extend_and_root_batched(s, m2, fused=False),
        in_shardings=in_sharding,
    )


# ---------------------------------------------------------------------- #
# Explicit-collective spelling (shard_map)


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _contraction_ops(k: int, sp: int, m2):
    """The two contraction pieces a row-sharded program needs — local
    row extension and the per-device column-contraction partial.

    Returns (encode_rows, q2_partial): `q2_partial` maps
    bits (k, 8*rows_per, B) -> (8k, k, B) int8 partial parities ALREADY
    reduced mod 2, ready for the int8 psum over 'sp'."""
    rows_per = k // sp

    def encode_rows(block):
        return rs_tpu.rs_encode_rows(block, m2)

    def q2_partial(bits):
        idx = jax.lax.axis_index("sp")
        # rows of m2 block-select: contraction index q = 8*row + bit,
        # where row is the GLOBAL row index of this block
        m2_block = jax.lax.dynamic_slice_in_dim(
            m2, idx * 8 * rows_per, 8 * rows_per, axis=1
        ).astype(jnp.int8)
        partial = jax.lax.dot_general(
            m2_block, bits,
            dimension_numbers=(((1,), (bits.ndim - 2,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (8k, k_cols, B)
        # mod-2 BEFORE the collective: (Σ partial) & 1 ==
        # (Σ (partial & 1)) & 1, so the psum ships int8 parities —
        # 4x less interconnect volume than the int32 counts.
        return (partial & 1).astype(jnp.int8)

    return encode_rows, q2_partial


def extend_and_root_rowsharded(mesh: Mesh, k: int):
    """One square, rows sharded over the 'sp' mesh axis; explicit psum /
    all_gather collectives. Returns a jitted fn of (k, k, 512) uint8."""
    m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))
    sp = mesh.shape["sp"]
    if k % sp:
        raise ValueError(f"square size {k} not divisible by sp={sp}")
    encode_rows, q2_partial = _contraction_ops(k, sp, m2)

    def local_fn(shares_block):  # (k/sp, k, 512) local rows
        # Q1: row extension is local to the row shard.
        q1 = encode_rows(shares_block)

        # Q2: contraction over the *sharded* row axis -> per-device
        # partial parities, psum over sp, reduce mod 2.
        cols_local = jnp.swapaxes(shares_block, 0, 1)  # (k, k/sp rows, 512)
        bits = rs_tpu.unpack_bits(cols_local)  # (k, 8*k/sp, B)
        idx = jax.lax.axis_index("sp")
        rows_per = k // sp
        total = jax.lax.psum(q2_partial(bits), "sp")
        q2_full = rs_tpu.pack_bits(jnp.moveaxis(total & 1, 0, -2))  # (k, k, B) cols-major
        q2 = jnp.swapaxes(q2_full, 0, 1)  # (k rows, k cols, 512), replicated

        # Q3: row-extend the local slice of Q2's rows.
        q2_local = jax.lax.dynamic_slice_in_dim(q2, idx * rows_per, rows_per, axis=0)
        q3_local = encode_rows(q2_local)

        # Assemble this device's row blocks of the EDS:
        top_local = jnp.concatenate([shares_block, q1], axis=1)  # rows of Q0|Q1
        bottom_local = jnp.concatenate([q2_local, q3_local], axis=1)  # rows of Q2|Q3

        # NMT: leaf digests for the local top and bottom row blocks.
        from celestia_tpu.appconsts import NAMESPACE_SIZE
        from celestia_tpu.ops.extend_tpu import (
            _PARITY_NS,
            merkle_root_pow2,
            nmt_leaf_nodes,
            nmt_reduce_axis,
        )

        parity = jnp.broadcast_to(jnp.asarray(_PARITY_NS),
                                  (rows_per, k, NAMESPACE_SIZE))
        top_ns = jnp.concatenate(
            [shares_block[..., :NAMESPACE_SIZE], parity], axis=1
        )
        bottom_ns = jnp.broadcast_to(jnp.asarray(_PARITY_NS),
                                     (rows_per, 2 * k, NAMESPACE_SIZE))
        top_leaves = nmt_leaf_nodes(top_ns, top_local)  # (rows_per, 2k, 90)
        bottom_leaves = nmt_leaf_nodes(bottom_ns, bottom_local)

        # Row roots: local reduction over each row's leaves.
        row_roots_local = jnp.concatenate(
            [nmt_reduce_axis(top_leaves), nmt_reduce_axis(bottom_leaves)], axis=0
        )  # (2*rows_per, 90) — this device's rows of Q0|Q1 and Q2|Q3

        # Column roots: need all rows' leaf digests -> all_gather the
        # (small) leaf node tensor, then reduce columns locally.
        top_all = jax.lax.all_gather(top_leaves, "sp", axis=0, tiled=True)
        bottom_all = jax.lax.all_gather(bottom_leaves, "sp", axis=0, tiled=True)
        all_leaves = jnp.concatenate([top_all, bottom_all], axis=0)  # (2k, 2k, 90)
        col_roots = nmt_reduce_axis(jnp.swapaxes(all_leaves, 0, 1))  # (2k, 90)

        # Gather row roots (each device holds interleaved top/bottom rows).
        top_roots_all = jax.lax.all_gather(
            row_roots_local[:rows_per], "sp", axis=0, tiled=True
        )
        bottom_roots_all = jax.lax.all_gather(
            row_roots_local[rows_per:], "sp", axis=0, tiled=True
        )
        row_roots = jnp.concatenate([top_roots_all, bottom_roots_all], axis=0)

        dah = merkle_root_pow2(jnp.concatenate([row_roots, col_roots], axis=0))
        eds_rows_local = jnp.concatenate([top_local, bottom_local], axis=0)
        return eds_rows_local, row_roots, col_roots, dah

    sharded = _shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P("sp", None, None),),
        out_specs=(P("sp", None, None), P(), P(), P()),
    )

    def reassemble(shares):
        eds_interleaved, row_roots, col_roots, dah = sharded(shares)
        # out rows are [dev0 top | dev0 bottom | dev1 top | ...]: restore
        # global order [all top rows, all bottom rows].
        rows_per = k // sp
        blocks = eds_interleaved.reshape(sp, 2 * rows_per, 2 * k, 512)
        top = blocks[:, :rows_per].reshape(k, 2 * k, 512)
        bottom = blocks[:, rows_per:].reshape(k, 2 * k, 512)
        return jnp.concatenate([top, bottom], axis=0), row_roots, col_roots, dah

    return jax.jit(reassemble)


def extend_root_levels_rowsharded(mesh: Mesh, k: int):
    """The block-pipeline hot path: extend + axis roots + EVERY row-tree
    level in ONE sharded program (node/pipeline.py's compute leg). The
    separate levels spelling re-hashes all (2k)² leaf digests the extend
    already computed; here the per-device leaf stacks feed both the root
    reductions and `nmt_reduce_levels`, so each leaf is SHA-256'd exactly
    once and the stream pays ONE sp-wide dispatch per block instead of
    two. Outputs are byte-identical to extend_and_root_rowsharded
    followed by eds_row_levels_rowsharded. Returns a jitted fn of
    (k, k, 512) uint8 -> (eds, row_roots, col_roots, dah, levels_tuple).
    """
    from celestia_tpu.appconsts import NAMESPACE_SIZE
    from celestia_tpu.ops.extend_tpu import (
        _PARITY_NS,
        merkle_root_pow2,
        nmt_leaf_nodes,
        nmt_reduce_axis,
        nmt_reduce_levels,
    )

    m2 = jnp.asarray(rs_tpu.encode_bit_matrix(k))
    sp = mesh.shape["sp"]
    if k % sp:
        raise ValueError(f"square size {k} not divisible by sp={sp}")
    rows_per = k // sp
    n_levels = (2 * k).bit_length()
    encode_rows, q2_partial = _contraction_ops(k, sp, m2)

    def local_fn(shares_block):  # (k/sp, k, 512) local rows
        q1 = encode_rows(shares_block)
        cols_local = jnp.swapaxes(shares_block, 0, 1)
        bits = rs_tpu.unpack_bits(cols_local)
        idx = jax.lax.axis_index("sp")
        # int8 parity psum, same mod-2 homomorphism as the unfused spelling
        total = jax.lax.psum(q2_partial(bits), "sp")
        q2_full = rs_tpu.pack_bits(jnp.moveaxis(total & 1, 0, -2))
        q2 = jnp.swapaxes(q2_full, 0, 1)
        q2_local = jax.lax.dynamic_slice_in_dim(q2, idx * rows_per, rows_per, axis=0)
        q3_local = encode_rows(q2_local)

        top_local = jnp.concatenate([shares_block, q1], axis=1)
        bottom_local = jnp.concatenate([q2_local, q3_local], axis=1)

        parity = jnp.broadcast_to(jnp.asarray(_PARITY_NS),
                                  (rows_per, k, NAMESPACE_SIZE))
        top_ns = jnp.concatenate(
            [shares_block[..., :NAMESPACE_SIZE], parity], axis=1
        )
        bottom_ns = jnp.broadcast_to(jnp.asarray(_PARITY_NS),
                                     (rows_per, 2 * k, NAMESPACE_SIZE))
        top_leaves = nmt_leaf_nodes(top_ns, top_local)
        bottom_leaves = nmt_leaf_nodes(bottom_ns, bottom_local)

        # The levels ride the SAME leaf stacks the roots reduce — this is
        # the fusion: no second leaf-hash pass, no second dispatch. The
        # local row roots ARE the top level of that stack (per-row
        # reduction commutes with the row concat), so the row trees are
        # hashed once, not re-reduced per root.
        levels_local = nmt_reduce_levels(
            jnp.concatenate([top_leaves, bottom_leaves], axis=0)
        )
        row_roots_local = levels_local[-1][:, 0, :]
        top_all = jax.lax.all_gather(top_leaves, "sp", axis=0, tiled=True)
        bottom_all = jax.lax.all_gather(bottom_leaves, "sp", axis=0, tiled=True)
        all_leaves = jnp.concatenate([top_all, bottom_all], axis=0)
        col_roots = nmt_reduce_axis(jnp.swapaxes(all_leaves, 0, 1))
        top_roots_all = jax.lax.all_gather(
            row_roots_local[:rows_per], "sp", axis=0, tiled=True
        )
        bottom_roots_all = jax.lax.all_gather(
            row_roots_local[rows_per:], "sp", axis=0, tiled=True
        )
        row_roots = jnp.concatenate([top_roots_all, bottom_roots_all], axis=0)
        dah = merkle_root_pow2(jnp.concatenate([row_roots, col_roots], axis=0))
        eds_rows_local = jnp.concatenate([top_local, bottom_local], axis=0)
        return eds_rows_local, row_roots, col_roots, dah, tuple(levels_local)

    sharded = _shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P("sp", None, None),),
        out_specs=(P("sp", None, None), P(), P(), P(),
                   tuple(P("sp", None, None) for _ in range(n_levels))),
    )

    def reassemble(shares):
        eds_interleaved, row_roots, col_roots, dah, levels = sharded(shares)

        # shard-order rows are [dev0 top | dev0 bottom | dev1 top | ...]:
        # restore global order [all top rows, all bottom rows] for the
        # EDS and every level alike.
        def deinterleave(arr):
            blocks = arr.reshape(sp, 2 * rows_per, *arr.shape[1:])
            top = blocks[:, :rows_per].reshape(k, *arr.shape[1:])
            bottom = blocks[:, rows_per:].reshape(k, *arr.shape[1:])
            return jnp.concatenate([top, bottom], axis=0)

        return (deinterleave(eds_interleaved), row_roots, col_roots, dah,
                tuple(deinterleave(lv) for lv in levels))

    return jax.jit(reassemble)


def eds_row_levels_rowsharded(mesh: Mesh, k: int):
    """Row-tree levels of an EXISTING (2k,2k,512) EDS, rows sharded over
    'sp'. Row trees are strictly per-row, so every level is computed
    locally on the device holding that row block and the level stack
    reassembles by plain row-order concatenation — no collectives at
    all, and the shards are byte-identical slices of what the
    single-chip `_jitted_row_levels` produces, so
    proof.NmtRowProver.from_node_levels seeds the same provers with
    zero host hashing. Returns a jitted fn of (2k,2k,512) uint8 ->
    tuple of (2k, 2k/2^L, 90) level arrays."""
    from celestia_tpu.appconsts import NAMESPACE_SIZE
    from celestia_tpu.ops.extend_tpu import (
        _PARITY_NS,
        nmt_leaf_nodes,
        nmt_reduce_levels,
    )

    w = 2 * k
    sp = mesh.shape["sp"]
    if w % sp:
        raise ValueError(f"EDS width {w} not divisible by sp={sp}")
    rows_per = w // sp
    n_levels = w.bit_length()  # leaves, w/2, ..., 1

    def local_fn(eds_rows):  # (rows_per, 2k, 512) local row block
        idx = jax.lax.axis_index("sp")
        row_global = idx * rows_per + jnp.arange(rows_per, dtype=jnp.int32)
        # wrapper namespace rule per cell: Q0 cells (row < k AND col < k)
        # keep their own namespace, every parity cell uses _PARITY_NS —
        # computable locally from the global row index of this block.
        is_q0 = (row_global[:, None] < k) & (
            jnp.arange(w, dtype=jnp.int32)[None, :] < k
        )
        parity = jnp.broadcast_to(jnp.asarray(_PARITY_NS),
                                  (rows_per, w, NAMESPACE_SIZE))
        leaf_ns = jnp.where(
            is_q0[..., None], eds_rows[..., :NAMESPACE_SIZE], parity
        )
        leaf_nodes = nmt_leaf_nodes(leaf_ns, eds_rows)  # (rows_per, 2k, 90)
        return tuple(nmt_reduce_levels(leaf_nodes))

    sharded = _shard_map(
        local_fn,
        mesh=mesh,
        in_specs=P("sp", None, None),
        out_specs=tuple(P("sp", None, None) for _ in range(n_levels)),
    )
    return jax.jit(sharded)
