"""Packing utilities and the jit'd wrapper for the joint kernel, plus the
bit-true DBMU reference entry point."""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core import fta
from .dbmu_sim import dbmu_matmul
from .joint_sparse_matmul import BK, BM as JBM, BN, joint_sparse_matmul


def random_tile_mask(rng, K: int, N: int, sparsity: float,
                     tile: int = 128) -> np.ndarray:
    """Whole-(tile x tile) random survival mask (ceil + crop, so ragged
    shapes work) — tile-granular value sparsity the kernels can actually
    skip. At least one tile always survives. Benchmarks and tests share
    this so their sparsity semantics cannot drift."""
    kt, nt = -(-K // tile), -(-N // tile)
    alive = rng.random((kt, nt)) >= sparsity
    if not alive.any():
        alive[0, 0] = True
    full = np.repeat(np.repeat(alive, tile, 0), tile, 1)
    return full[:K, :N].astype(np.int32)


def tile_prune_mask_balanced(w: np.ndarray, value_sparsity: float,
                             bk: int = BK, bn: int = BN) -> np.ndarray:
    """Column-balanced tile pruning: drop the lowest-L2 ``round(vs * kt)``
    K-tiles in EVERY N-tile column (ceil + crop for ragged shapes).

    This is the MXU mapping of the paper's 1 x alpha sparse allocation
    network: the unit the joint kernel can SKIP is a whole weight tile,
    and finer 1 x alpha pruning (core.pruning, used for the accuracy
    experiments) essentially never empties one. Every column keeps
    exactly the same number of K-blocks — so MAXB == the survivor count,
    the packed layout carries ZERO padded slots, and a whole layer stack
    packs to one shared MAXB.
    This is the uniformity SparseP-style PIM serving needs: stored bytes
    equal ``(1 - vs)`` of dense exactly, per layer, per column.
    """
    K, N = w.shape
    kt, nt = -(-K // bk), -(-N // bn)
    wp = np.zeros((kt * bk, nt * bn), np.float32)
    wp[:K, :N] = w
    norms = (wp.reshape(kt, bk, nt, bn) ** 2).sum(axis=(1, 3))   # (kt, nt)
    n_drop = min(int(round(value_sparsity * kt)), kt - 1)
    alive = np.ones((kt, nt), bool)
    if n_drop > 0:
        order = np.argsort(norms, axis=0)                        # ascending
        for c in range(nt):
            alive[order[:n_drop, c], c] = False
    full = np.repeat(np.repeat(alive, bk, 0), bn, 1)[:K, :N]
    return full.astype(np.int32)


def quantize_int8_fta(w: np.ndarray, mask: np.ndarray,
                      fta_project: bool = True):
    """The bit-level compression step, shared by every packing path:
    per-filter symmetric INT8 scale over the kept weights, then (unless
    fta_project=False) the FTA projection, so the INT8 grid is exactly
    servable by the PIM macro.

    Returns (q int32 (K, N) masked + on the grid, scales f32 (1, N)).
    """
    m = np.asarray(mask, np.int32)
    amax = np.abs(w * m).max(axis=0)
    scales = (amax / 127.0 + 1e-12).astype(np.float32)
    q = np.clip(np.round(w * m / scales), -127, 127).astype(np.int32)
    if fta_project:
        q, _phi = fta.fta_quantize(q, m)
        q = np.asarray(q)
    return q * m, scales.reshape(1, -1)

class JointPacked(NamedTuple):
    """Compacted + quantized weight artifact for joint_sparse_matmul.

    ``w_blocks`` (NT, MAXB, bk, bn) int8 / ``idx`` (NT, MAXB) int32 /
    ``scales`` (1, N_pad) f32 / ``nblocks`` (NT,) int32 real blocks per
    tile (slots past it are zero payload). ``k``/``n`` are the original
    logical dims, ``k_pad`` the padded K the index table refers to.
    """
    w_blocks: jnp.ndarray
    idx: jnp.ndarray
    scales: jnp.ndarray
    nblocks: jnp.ndarray
    k: int
    n: int
    k_pad: int


def _tile_alive(m: np.ndarray, bk: int, bn: int) -> np.ndarray:
    """(kt, nt) bool: does the (bk, bn) mask tile keep any weight? Pads
    ragged shapes with zeros — THE survivor rule, shared by the per-layer
    pack and the stacked shared-MAXB pre-pass so they cannot drift."""
    K, N = m.shape
    kt, nt = -(-K // bk), -(-N // bn)
    mp = np.zeros((kt * bk, nt * bn), np.int32)
    mp[:K, :N] = m
    return mp.reshape(kt, bk, nt, bn).sum(axis=(1, 3)) > 0


def _quantize_and_compact(w, m, bk, bn, fta_project, maxb=None,
                          payload: str = "int8"):
    """Pad -> quantize -> compact one 2D layer. Returns numpy
    (w_blocks, idx, nblocks, scales, Kp, Np). maxb forces the slot count
    (stacked packs share one MAXB across layers); None uses this layer's
    own survivor maximum.

    payload "int8" is the joint/bit-level artifact (INT8 on the
    per-filter FTA scale grid); "bf16" keeps the surviving weights as
    raw bf16 with unit scales — the VALUE-ONLY serving layout, same
    compaction/index structure, no bit-level compression."""
    alive = _tile_alive(m, bk, bn)                              # (kt, nt)
    K, N = w.shape
    kp, npad = (-K) % bk, (-N) % bn
    w = np.pad(w, ((0, kp), (0, npad)))
    m = np.pad(m, ((0, kp), (0, npad)))
    Kp, Np = w.shape

    if payload == "int8":
        q, scales = quantize_int8_fta(w, m, fta_project=fta_project)
        q = q.astype(np.int8)
        pay_dtype = np.int8
    elif payload == "bf16":
        q = np.asarray(jnp.asarray(w * m, jnp.bfloat16))
        scales = np.ones((1, Np), np.float32)
        pay_dtype = q.dtype
    else:
        raise ValueError(f"payload {payload!r} not in ('int8', 'bf16')")

    kt, nt = Kp // bk, Np // bn
    if maxb is None:
        maxb = max(int(alive.sum(axis=0).max()), 1)
    tiles = q.reshape(kt, bk, nt, bn)
    w_blocks = np.zeros((nt, maxb, bk, bn), pay_dtype)
    idx = np.zeros((nt, maxb), np.int32)
    nblocks = np.zeros((nt,), np.int32)
    for n_t in range(nt):
        rows = np.nonzero(alive[:, n_t])[0]
        nblocks[n_t] = rows.size
        for b, kblk in enumerate(rows):
            w_blocks[n_t, b] = tiles[kblk, :, n_t, :]
            idx[n_t, b] = kblk
    return w_blocks, idx, nblocks, scales.reshape(1, Np), Kp, Np


def pack_joint_sparse(w_dense, mask=None, *, bk: int = BK, bn: int = BN,
                      value_sparsity: float = None,
                      fta_project: bool = True) -> JointPacked:
    """Full joint compilation: prune -> INT8/FTA quantize -> compact.

    A K-block survives for an N tile iff the (bk, bn) mask tile keeps any
    weight. When no mask is given and value_sparsity is set, pruning
    happens at (bk, bn) TILE granularity (tile_prune_mask_balanced) — the
    unit the kernel can skip. Surviving payload is INT8 on the
    per-filter-scale grid (FTA projection keeps it exactly
    representable); K and N are zero-padded to the tile size, so odd
    shapes pack fine.
    """
    w = np.asarray(w_dense, np.float32)
    K, N = w.shape
    if mask is None:
        m = (tile_prune_mask_balanced(w, value_sparsity, bk, bn)
             if value_sparsity else np.ones_like(w, np.int32))
    else:
        m = np.asarray(mask, np.int32)
    w_blocks, idx, nblocks, scales, Kp, _ = _quantize_and_compact(
        w, m, bk, bn, fta_project)
    return JointPacked(jnp.asarray(w_blocks), jnp.asarray(idx),
                       jnp.asarray(scales), jnp.asarray(nblocks), K, N, Kp)


class JointPackedStacked(NamedTuple):
    """Joint artifact for ALL L layers of one projection family, packed
    with one shared MAXB (= max survivors over layers; slots past a
    layer's real block count are zero payload, which the kernel treats as
    exact zeros). Every field is a single stacked array with a leading
    layer axis — the layout ``lax.scan`` can carry as per-layer xs, which
    is what lets the serving graph run the joint kernel end-to-end
    instead of per-layer.

    ``w_blocks`` (L, NT, MAXB, bk, bn) int8 / ``idx`` (L, NT, MAXB) int32
    / ``scales`` (L, 1, N_pad) f32 / ``nblocks`` (L, NT) int32.
    ``k``/``n``/``k_pad`` are shared static dims (identical across the
    stack by construction).
    """
    w_blocks: jnp.ndarray
    idx: jnp.ndarray
    scales: jnp.ndarray
    nblocks: jnp.ndarray
    k: int
    n: int
    k_pad: int

    @property
    def maxb(self) -> int:
        return self.w_blocks.shape[2]


def pack_joint_sparse_stacked(w_stack, masks=None, *, bk: int = BK,
                              bn: int = BN, value_sparsity: float = None,
                              fta_project: bool = True,
                              payload: str = "int8",
                              ) -> JointPackedStacked:
    """Stack-uniform joint compilation of (L, K, N) layer weights.

    Per layer: column-balanced tile pruning (``tile_prune_mask_balanced``
    — every N-tile column keeps the same number of K-blocks, so with no
    explicit masks MAXB is exactly ``kt - round(vs * kt)`` and NO padded
    slots exist anywhere in the stack) -> per-filter INT8/FTA
    quantization -> compaction into the shared-MAXB layout. With explicit
    ragged ``masks`` (L, K, N), MAXB is the max survivor count over the
    whole stack and short layers pad with zero-payload slots.

    payload "bf16" skips the bit level: surviving blocks carry the raw
    bf16 weights with unit scales — the value-ONLY serving layout
    (weight traffic (1 - vs) of dense bf16 instead of (1 - vs) * 0.5).
    The kernel is payload-dtype-agnostic (it dequantizes whatever the
    blocks hold to the activation dtype), so both layouts serve through
    the same ``joint_dense`` path.
    """
    w_stack = np.asarray(w_stack, np.float32)
    if w_stack.ndim != 3 or not w_stack.shape[0]:
        raise ValueError(f"w_stack must be (L, K, N), got {w_stack.shape}")
    L, K, N = w_stack.shape
    if masks is None:
        ms = [(tile_prune_mask_balanced(w_stack[l], value_sparsity, bk, bn)
               if value_sparsity else np.ones((K, N), np.int32))
              for l in range(L)]
    else:
        ms = [np.asarray(np.asarray(masks)[l], np.int32) for l in range(L)]

    # shared MAXB: max surviving K-blocks over every (layer, column) pair
    maxb = max(1, max(int(_tile_alive(ms[l], bk, bn).sum(axis=0).max())
                      for l in range(L)))

    wbs, idxs, nbs, scs = [], [], [], []
    for l in range(L):
        wb, idx, nb, sc, Kp, _ = _quantize_and_compact(
            w_stack[l], ms[l], bk, bn, fta_project, maxb=maxb,
            payload=payload)
        wbs.append(wb)
        idxs.append(idx)
        nbs.append(nb)
        scs.append(sc)
    return JointPackedStacked(
        jnp.asarray(np.stack(wbs)), jnp.asarray(np.stack(idxs)),
        jnp.asarray(np.stack(scs)), jnp.asarray(np.stack(nbs)),
        K, N, Kp)


class JointPackedGrouped(NamedTuple):
    """Joint artifact for a GROUPED projection family: all L layers x E
    group members (MoE experts) of one projection, packed with ONE shared
    MAXB over every (layer, member) pair. The leading layer axis rides a
    ``lax.scan`` exactly like JointPackedStacked; the second (group) axis
    is sliced by the per-expert dispatch loop inside the scan body.

    ``w_blocks`` (L, E, NT, MAXB, bk, bn) int8|bf16 / ``idx`` (L, E, NT,
    MAXB) int32 / ``scales`` (L, E, 1, N_pad) f32 / ``nblocks`` (L, E,
    NT) int32. ``k``/``n``/``k_pad`` are shared static dims.
    """
    w_blocks: jnp.ndarray
    idx: jnp.ndarray
    scales: jnp.ndarray
    nblocks: jnp.ndarray
    k: int
    n: int
    k_pad: int

    @property
    def maxb(self) -> int:
        return self.w_blocks.shape[3]


def pack_joint_sparse_grouped(w_group, masks=None, *, bk: int = BK,
                              bn: int = BN, value_sparsity: float = None,
                              fta_project: bool = True,
                              payload: str = "int8",
                              ) -> JointPackedGrouped:
    """Group-uniform joint compilation of (L, E, K, N) expert weights.

    The grouped pack is the stacked pack over the FLATTENED (L * E) axis
    — column-balanced tile pruning (``tile_prune_mask_balanced``) per
    (layer, expert) slice, per-filter INT8/FTA quantization, compaction —
    with the shared MAXB taken over every layer of every expert, then the
    (L, E) axes restored. Balanced self-pruning keeps every expert's
    survivor count identical per N-column, so MAXB == ``kt - round(vs *
    kt)`` with ZERO padded slots anywhere in the group; explicit ragged
    ``masks`` (L, E, K, N) pad short members with zero-payload slots.
    payload "bf16" is the value-only layout, exactly as in the stacked
    pack.
    """
    w_group = np.asarray(w_group, np.float32)
    if w_group.ndim != 4 or not (w_group.shape[0] and w_group.shape[1]):
        raise ValueError(f"w_group must be (L, E, K, N), "
                         f"got {w_group.shape}")
    L, E, K, N = w_group.shape
    flat_masks = None
    if masks is not None:
        flat_masks = np.asarray(masks, np.int32).reshape(L * E, K, N)
    flat = pack_joint_sparse_stacked(
        w_group.reshape(L * E, K, N), flat_masks, bk=bk, bn=bn,
        value_sparsity=value_sparsity, fta_project=fta_project,
        payload=payload)
    regroup = lambda a: a.reshape((L, E) + a.shape[1:])
    return JointPackedGrouped(
        regroup(flat.w_blocks), regroup(flat.idx), regroup(flat.scales),
        regroup(flat.nblocks), flat.k, flat.n, flat.k_pad)


def slice_joint_grouped(packed: JointPackedGrouped, l: int,
                        e: int) -> JointPacked:
    """Expert e of layer l as a per-projection JointPacked view."""
    return JointPacked(packed.w_blocks[l, e], packed.idx[l, e],
                       packed.scales[l, e], packed.nblocks[l, e],
                       packed.k, packed.n, packed.k_pad)


def unpack_joint_sparse_grouped(packed: JointPackedGrouped) -> np.ndarray:
    """Invert pack_joint_sparse_grouped -> dense fp32 (L, E, K, N)."""
    L, E = packed.w_blocks.shape[:2]
    return np.stack([
        np.stack([unpack_joint_sparse(slice_joint_grouped(packed, l, e))
                  for e in range(E)]) for l in range(L)])


def slice_joint_stacked(packed: JointPackedStacked, l: int) -> JointPacked:
    """Layer l's view of a stacked pack (the scan body does the same
    slicing implicitly through its xs)."""
    return JointPacked(packed.w_blocks[l], packed.idx[l], packed.scales[l],
                       packed.nblocks[l], packed.k, packed.n, packed.k_pad)


def unpack_joint_sparse_stacked(packed: JointPackedStacked) -> np.ndarray:
    """Invert pack_joint_sparse_stacked -> dense fp32 (L, K, N)."""
    return np.stack([unpack_joint_sparse(slice_joint_stacked(packed, l))
                     for l in range(packed.w_blocks.shape[0])])


def unpack_joint_sparse(packed: JointPacked) -> np.ndarray:
    """Invert pack_joint_sparse -> dense fp32 (K, N) == q * mask * scale.
    Payload-dtype-agnostic: int8 (joint/bit) and bf16 (value-only) blocks
    both scatter exactly into f32."""
    wb = np.asarray(packed.w_blocks).astype(np.float32)
    idx = np.asarray(packed.idx)
    nb = np.asarray(packed.nblocks)
    nt, _, bk, bn = wb.shape
    dense = np.zeros((packed.k_pad, nt * bn), np.float32)
    for n_t in range(nt):
        for b in range(int(nb[n_t])):
            kblk = int(idx[n_t, b])
            dense[kblk * bk:(kblk + 1) * bk,
                  n_t * bn:(n_t + 1) * bn] = wb[n_t, b]
    dense *= np.asarray(packed.scales)
    return dense[:packed.k, :packed.n]


def joint_storage_bytes(packed) -> int:
    """HBM bytes of a joint artifact (payload + index + scales); accepts
    JointPacked or JointPackedStacked (same field names)."""
    return int(packed.w_blocks.size + packed.idx.size * 4
               + packed.scales.size * 4)


def pick_row_tile(m: int, dtype) -> int:
    """Decode-tuned row tile: full 128-row MXU tiles for big batches, the
    smallest legal sublane multiple for small ones — a batch-4 decode
    step pads its activations to 8 (f32) / 16 (bf16) rows, not 128."""
    if m >= JBM:
        return JBM
    sub = 8 if jnp.dtype(dtype).itemsize >= 4 else 16
    return max(sub, sub * (-(-m // sub)))


def joint_dense(x, packed: JointPacked, interpret: bool = None,
                bm: int = None):
    """Public op: joint value x bit sparse y = x @ W for 2D/3D activations.

    Pads M to the kernel row tile and K to the packed K (both zero — padded
    K columns hit only pruned weight rows), slices the result back.
    bm=None picks the row tile from M (small-M decode tile; see
    pick_row_tile); interpret=None uses the backend default.
    """
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    M, K = x2.shape
    if K != packed.k:
        raise ValueError(f"activation K={K} != packed k={packed.k}")
    if bm is None:
        bm = pick_row_tile(M, x.dtype)
    mp = (-M) % bm
    x2 = jnp.pad(x2, ((0, mp), (0, packed.k_pad - K)))
    y = joint_sparse_matmul(x2, packed.w_blocks, packed.idx, packed.scales,
                            bm=bm, interpret=interpret)
    y = y[:M, :packed.n]
    return y.reshape(shape[:-1] + (packed.n,))


def dbmu_reference_check(x_int8, packed, interpret: bool = None):
    """Run the bit-true DBMU datapath."""
    return dbmu_matmul(jnp.asarray(x_int8, jnp.int32),
                       jnp.asarray(packed), interpret=interpret)
