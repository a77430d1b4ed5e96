"""KV-cache memory manager: one storage format, the block pool.

Orca/vLLM-style continuous batching needs per-sequence key/value state
that outlives any single forward call and can be handed to a *different*
sequence the moment its owner retires. The device arrays are a pool
``[num_blocks, block_size, H_kv, D]`` and each sequence owns an ordered
*block table* of pool indices (`write_kv_paged`, `paged_attention`,
`BlockPool`, `PagedKVCache`). Virtual position ``p`` of a sequence
lives at ``pool[table[p // bs], p % bs]``; attention gathers the table
and applies a positional validity mask, so occupancy is bounded by
**tokens resident** (blocks actually allocated), not ``rows x
max_len``. Blocks are refcounted, which is what lets the radix prefix
cache (serve/prefix.py) share read-only prompt-prefix runs across
sequences.

A cache with one fixed ``max_len`` row a sequence is the special case
of a pool as large as the worst case read through a table that never
changes: what a model config without pool sizes gets (the executor
works the size out, serve/executor.py) and how the speculative drafter
reads its cache (serve/batcher.py).

Shapes never depend on which rows/blocks are live — liveness is data
(masks, tables, positions), so jit compiles each program exactly once
(the no-recompile contract, docs/serving.md).

The device arrays themselves live in the model's flax ``"cache"``
collection (`write_kv_pools`, called by `pool_attention` for the
decode paths of models/gpt.py and models/llama.py and by
models/routed_lm.py) and are threaded through the executor
(serve/executor.py); this module holds no jax arrays of its own. They
are HELD padded to whole device tiles (`held_pool_shape`), the shape
the device lays out in the row-major order the scatter and the kernel
read.
"""
from __future__ import annotations

import zlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: tokens a pool block holds where a decode-mode model config names no
#: size of its own (the GPT-2 serve cell's)
KV_BLOCK_SIZE = 16

#: additive mask for invalid key positions — large-negative rather than
#: -inf so fully-masked garbage rows (inactive rows) still softmax to
#: finite numbers instead of NaN
_MASK_VALUE = -1e30


def masked_attention(q: jax.Array, keys: jax.Array, vals: jax.Array,
                     positions: jax.Array,
                     window: Optional[int] = None) -> jax.Array:
    """THE masked-attention contract — the single reference
    implementation shared by every decode read in the tree.

    Causal attention of `T` query tokens over each row's
    ``[B, L, H_kv, D]`` key/value view, valid positions
    ``[0, positions[b] + t]`` only (with a ``window``, the newest
    ``window`` of them); f32 score math, divide-after-dot
    ``1/sqrt(D)`` scaling, large-negative additive masking, output cast
    back to ``q.dtype``. `paged_attention` (the gathered-pool XLA path)
    and through it the models' decode attention delegate here, and the
    fused Pallas kernels (ops/pallas_paged.py) mirror this math
    operation-for-operation —
    it is the bit-exactness ORACLE the interpret-mode parity suite
    asserts against (tests/test_serve_kernels.py).

    The GQA group is folded into the matmul M dimension
    (``[T * G, D] x [L, D]`` per (row, kv head), exactly the kernel's
    slice shapes) rather than repeating K/V to H heads: batched
    `dot_general` over (B, KV) and the kernel's per-program dot then
    hit the same XLA gemm micro-kernels, which is what makes bit-match
    achievable at all (micro-kernel choice is shape-dependent).
    """
    B, T, H, D = q.shape
    L, KV = keys.shape[1], keys.shape[2]
    G = H // KV
    # [B, T, H, D] -> [B, KV, T*G, D]; row order t*G + g matches the
    # kernel's [T, G, D] block flattening
    qf = q.astype(jnp.float32).reshape(B, T, KV, G, D).transpose(
        0, 2, 1, 3, 4).reshape(B, KV, T * G, D)
    kf = keys.astype(jnp.float32).transpose(0, 2, 1, 3)   # [B, KV, L, D]
    vf = vals.astype(jnp.float32).transpose(0, 2, 1, 3)
    scores = jax.lax.dot_general(
        qf, kf, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32) / np.sqrt(D)  # [B, KV, TG, L]
    t_of = jnp.arange(T * G) // G
    q_pos = positions[:, None, None, None] + t_of[None, None, :, None]
    valid = jnp.arange(L)[None, None, None, :] <= q_pos
    if window is not None:
        valid &= jnp.arange(L)[None, None, None, :] > q_pos - window
    scores = jnp.where(valid, scores, _MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jax.lax.dot_general(
        probs, vf, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)               # [B, KV, TG, D]
    out = out.reshape(B, KV, T, G, D).transpose(
        0, 2, 1, 3, 4).reshape(B, T, H, D)
    return out.astype(q.dtype)


# -- paged (block) storage ---------------------------------------------------

def _paged_slots(NB: int, BS: int, T: int, positions: jax.Array,
                 update_mask: jax.Array, block_tables: jax.Array):
    """Flat pool slot ``block * BS + offset`` of each of the ``T`` new
    tokens of each row ([B * T]); a write that must not land gets the
    out-of-range slot ``NB * BS`` (see `write_kv_paged`)."""
    nblk = block_tables.shape[1]
    abs_pos = positions[:, None] + jnp.arange(T, dtype=positions.dtype)[None]
    blk_idx = abs_pos // BS                                   # [B, T]
    off = abs_pos % BS
    safe_idx = jnp.clip(blk_idx, 0, nblk - 1)
    blocks = jnp.take_along_axis(block_tables, safe_idx, axis=1)  # [B, T]
    valid = (update_mask[:, None] & (blk_idx < nblk) & (blocks >= 0))
    flat = blocks * BS + off
    # invalid writes get an out-of-range index and mode="drop" discards
    # them at the scatter (deterministic on every backend)
    return jnp.where(valid, flat, NB * BS).reshape(-1)


def _scatter_slots(pool: jax.Array, new: jax.Array, flat: jax.Array):
    """``new`` [B, T, ...] written at the flat slots of ``pool`` [NB, BS,
    ...]; out-of-range slots dropped."""
    NB, BS = pool.shape[0], pool.shape[1]
    out = pool.reshape(NB * BS, *pool.shape[2:]).at[flat].set(
        new.reshape(-1, *new.shape[2:]).astype(pool.dtype), mode="drop")
    return out.reshape(pool.shape)


def write_kv_paged(pool_k: jax.Array, pool_v: jax.Array, k_new: jax.Array,
                   v_new: jax.Array, positions: jax.Array,
                   update_mask: jax.Array, block_tables: jax.Array):
    """Scatter `T` new K/V vectors per row into the block pool.

    pool_k/pool_v: [num_blocks, block_size, H_kv, D]; k_new/v_new:
    [B, T, H_kv, D]; positions: [B] int32 — row b's token t lands at
    virtual position positions[b] + t, i.e. pool slot
    ``(block_tables[b, p // bs], p % bs)``; block_tables:
    [B, blocks_per_seq] int32, -1 for unassigned entries. Writes whose
    row mask is False, whose virtual position runs past the table, or
    whose table entry is -1 are DROPPED (never land anywhere), which is
    what keeps bucket-padding garbage out of other sequences' blocks.
    Returns the updated (pool_k, pool_v).
    """
    flat = _paged_slots(pool_k.shape[0], pool_k.shape[1], k_new.shape[1],
                        positions, update_mask, block_tables)
    return _scatter_slots(pool_k, k_new, flat), \
        _scatter_slots(pool_v, v_new, flat)


def write_paged(pool: jax.Array, new: jax.Array, positions: jax.Array,
                update_mask: jax.Array, block_tables: jax.Array):
    """`write_kv_paged` for ONE pool of any per-token shape: ``pool``
    [num_blocks, block_size, ...], ``new`` [B, T, ...] (a latent pool's
    one vector a token, models/mla_lm.py)."""
    flat = _paged_slots(pool.shape[0], pool.shape[1], new.shape[1],
                        positions, update_mask, block_tables)
    return _scatter_slots(pool, new, flat)


def paged_attention(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                    block_tables: jax.Array, positions: jax.Array,
                    window: Optional[int] = None,
                    kv_heads: Optional[int] = None) -> jax.Array:
    """Block-table-aware masked attention over the pooled cache.

    Gathers each row's blocks into a contiguous
    ``[B, blocks_per_seq * block_size, H_kv, D]`` view and applies the
    positional validity mask of `masked_attention`. Unassigned table
    entries (-1) are sanitized to block 0; whatever they gather is
    unreachable — a sequence's valid prefix never extends past its
    assigned blocks. ``window``: each query sees its newest ``window``
    keys only (the fused kernel's argument of the same name).
    ``kv_heads``: the pools are HELD (`write_kv_pools`) and their first
    ``kv_heads`` heads and ``D`` lanes are the values.
    """
    if kv_heads is not None:
        pool_k = held_values(pool_k, kv_heads, q.shape[-1])
        pool_v = held_values(pool_v, kv_heads, q.shape[-1])
    NB, BS = pool_k.shape[0], pool_k.shape[1]
    B, nblk = block_tables.shape
    tbl = jnp.maximum(block_tables, 0)
    keys = pool_k[tbl].reshape(B, nblk * BS, *pool_k.shape[2:])
    vals = pool_v[tbl].reshape(B, nblk * BS, *pool_v.shape[2:])
    return masked_attention(q, keys, vals, positions, window)


#: a device tile: 128 lanes by 8 sublanes
POOL_LANES, POOL_SUBLANES = 128, 8


def held_pool_shape(pool_blocks: int, block_size: int, kv_heads: int,
                    head_dim: int) -> Tuple[int, int, int, int]:
    """The shape a ``[pool_blocks, block_size, kv_heads, head_dim]`` KV
    pool is HELD in (see `write_kv_pools`): ``head_dim`` padded up to
    whole lane tiles and ``kv_heads`` to whole sublane tiles (1, 2 and
    4 heads have tiles of their own), which is what a TPU lays out
    row-major: ``[.., 25, 64]`` is held as ``[.., 32, 128]``,
    ``[.., 4, 128]`` as it is. Heads so narrow that whole lanes would
    more than double them (no model's; the tests') are left alone."""
    lanes = -(-head_dim // POOL_LANES) * POOL_LANES
    if lanes > 2 * head_dim:
        return pool_blocks, block_size, kv_heads, head_dim
    rows = kv_heads if kv_heads in (1, 2, 4) else \
        -(-kv_heads // POOL_SUBLANES) * POOL_SUBLANES
    return pool_blocks, block_size, rows, lanes


def held_values(pool: jax.Array, kv_heads: int, head_dim: int) -> jax.Array:
    """The values of a pool HELD in `held_pool_shape`: its ``[..,
    :kv_heads, :head_dim]`` corner (on the chip a bitcast, no copy)."""
    return pool[:, :, :kv_heads, :head_dim]


def write_kv_pools(module, cfg, k: jax.Array, v: jax.Array,
                   positions: jax.Array, update_mask: jax.Array,
                   block_tables: jax.Array):
    """One layer's K and V pools (the ``"cache"`` collection of the flax
    ``module`` that calls this) with the ``T`` new tokens written
    through the tables -> ``(pool_k, pool_v)``, as HELD. k/v:
    [B, T, H_kv, D].

    The pools are HELD in `held_pool_shape`, the new rows zero-padded
    to it; the values are the ``[.., :H_kv, :D]`` corner
    (`held_values`), which the XLA reads take, while the paged kernel
    copies whole held blocks (a DMA of the corner's rows is not
    tile-aligned) and reads the corner in VMEM. The
    held shape is what decides a pool's layout on the device: a TPU
    lays a ``[.., H_kv, 64]`` array out with the BLOCK index
    minor-most (64 half-fills a lane tile), and every layer of every
    step then copied K and V whole to the row-major order the scatter
    and the paged kernel read, and back. Padded to whole tiles the
    device's own layout IS that row-major order, byte for byte what a
    row-major ``[.., H_kv, 64]`` pool is (which pads the same lanes and
    sublanes), so taking the corner is a bitcast and no step copies a
    pool. Naming the layout on the jitted steps instead
    (`jax.experimental.layout.Format`) does the same in a freshly
    compiled program, and is lost when the program comes back from
    jax's persistent compilation cache. Where the shape already fills
    whole tiles nothing is padded and the program is as it was."""
    KV, D = k.shape[-2:]
    pool = held_pool_shape(cfg.kv_pool_blocks, cfg.kv_block_size, KV, D)
    ck = module.variable("cache", "k", jnp.zeros, pool, cfg.dtype)
    cv = module.variable("cache", "v", jnp.zeros, pool, cfg.dtype)
    if pool[2:] != (KV, D):
        tiles = ((0, 0), (0, 0), (0, pool[2] - KV), (0, pool[3] - D))
        k, v = jnp.pad(k, tiles), jnp.pad(v, tiles)
    ck.value, cv.value = write_kv_paged(
        ck.value, cv.value, k, v, positions, update_mask, block_tables)
    return ck.value, cv.value


def pool_attention(module, cfg, q: jax.Array, k: jax.Array, v: jax.Array,
                   positions: jax.Array, update_mask: jax.Array,
                   block_tables: jax.Array) -> jax.Array:
    """One layer's decode attention over its block pool — the body
    models/gpt.py and models/llama.py share: the layer's K and V pools
    (``[kv_pool_blocks, kv_block_size, H_kv, D]``, in the ``"cache"``
    collection of the flax ``module`` that calls this), the ``T`` new
    tokens written through the tables, then each row's attention over
    its blocks by the kernel the executor resolved
    (``cfg.decode_kernel``: the fused Pallas kernel, or
    `paged_attention`). q: [B, T, H, D]; k/v: [B, T, H_kv, D]."""
    if block_tables is None:
        raise ValueError(
            "decode needs per-row `block_tables` "
            "(see horovod_tpu/serve/executor.py)")
    if cfg.kv_pool_blocks < 1:
        raise ValueError(
            "kv_pool_blocks is not set: name it in the model config, "
            "or build the model's ShardedExecutor first (it sizes the "
            "pool for max_batch x max_len)")
    pool_k, pool_v = write_kv_pools(module, cfg, k, v, positions,
                                    update_mask, block_tables)
    if cfg.decode_kernel == "pallas":
        from ..ops.pallas_paged import paged_attention_fused
        return paged_attention_fused(q, pool_k, pool_v, block_tables,
                                     positions, kv_heads=k.shape[2])
    return paged_attention(q, pool_k, pool_v, block_tables, positions,
                           kv_heads=k.shape[2])


def pool_blocks_for(max_batch: int, max_len: int, block_size: int,
                    fraction: float = 0.5) -> int:
    """A sane device pool size: ``fraction`` of the
    ``max_batch x max_len`` worst case (the whole point of paging is to
    provision for tokens actually resident), floored so every row can
    hold at least one block plus headroom for a shared prefix run."""
    worst = max_batch * -(-max_len // block_size)
    want = int(worst * fraction)
    return max(want, 2 * max_batch, -(-max_len // block_size) + max_batch)


class BlockPool:
    """Host-side free-list allocator over the device block pool.

    Blocks are REFCOUNTED: a block is held by the sequence that wrote
    it, plus one count per radix-prefix-cache node referencing it, plus
    one per additional sequence sharing it. It returns to the free list
    only when the last reference drops, so a shared system-prompt run
    can never be handed to a new owner while anyone still reads it.

    Also owns the per-BLOCK crc ledger: one running crc32 per cache leaf per block
    over the block's written prefix (``filled`` positions). Keyed by
    pool index, so a shared block carries ONE ledger entry no matter
    how many sequences reference it, and verify-on-read of a sequence
    covers its shared prefix for free.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1; got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1; got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # LIFO reuse: the most recently freed block is re-issued first
        self._free: List[int] = list(range(num_blocks))[::-1]
        self.refcount = np.zeros(num_blocks, dtype=np.int32)
        self.allocs = 0
        self.frees = 0
        self.peak_in_use = 0
        #: block -> (filled positions, [running crc32 per cache leaf])
        self._crc: Dict[int, Tuple[int, List[int]]] = {}
        #: ``on_shared(blk, +1)`` when a block's refcount rises from 1 to
        #: 2, ``(blk, -1)`` when it falls back: the radix prefix cache
        #: (serve/prefix.py) keeps its evictable count from these
        self.on_shared: Optional[Callable[[int, int], None]] = None

    # -- allocation ----------------------------------------------------------
    def alloc(self) -> Optional[int]:
        """Claim a free block (None when exhausted); refcount starts at
        1 (the caller's reference). Stale bytes need no clearing —
        positional masking makes them unreachable."""
        if not self._free:
            return None
        blk = self._free.pop()
        assert self.refcount[blk] == 0, \
            f"free list handed out in-use block {blk}"
        self.refcount[blk] = 1
        self.allocs += 1
        self._crc.pop(blk, None)
        self.peak_in_use = max(self.peak_in_use, self.in_use())
        return blk

    def incref(self, blk: int) -> None:
        rc = self.refcount[blk]
        if rc < 1:
            raise ValueError(f"block {blk} is not live")
        self.refcount[blk] = rc + 1
        if rc == 1 and self.on_shared is not None:
            self.on_shared(blk, 1)

    def decref(self, blk: int) -> bool:
        """Drop one reference; returns True when the block was freed."""
        rc = self.refcount[blk]
        if rc < 1:
            raise ValueError(f"block {blk} is not live")
        self.refcount[blk] = rc - 1
        if rc == 2 and self.on_shared is not None:
            self.on_shared(blk, -1)
        if rc == 1:
            self._free.append(blk)
            self.frees += 1
            self._crc.pop(blk, None)
            return True
        return False

    def in_use(self) -> int:
        return self.num_blocks - len(self._free)

    def free_count(self) -> int:
        return len(self._free)

    def occupancy(self) -> float:
        return self.in_use() / self.num_blocks

    # -- per-block integrity ledger ------------------------------------------
    def crc_filled(self, blk: int) -> int:
        ent = self._crc.get(blk)
        return 0 if ent is None else ent[0]

    def crc_stream(self, blk: int, leaf_bytes: Sequence[bytes],
                   new_filled: int) -> None:
        """Fold bytes just written at positions [filled, new_filled) of
        ``blk`` (one entry per cache leaf, leaf order) into the block's
        running crcs. The caller guarantees the bytes ARE that range."""
        ent = self._crc.get(blk)
        crcs = [0] * len(leaf_bytes) if ent is None else ent[1]
        for i, raw in enumerate(leaf_bytes):
            crcs[i] = zlib.crc32(raw, crcs[i])
        self._crc[blk] = (new_filled, crcs)

    def crc_reset(self, blk: int, leaf_bytes: Sequence[bytes],
                  filled: int) -> None:
        """Recompute the ledger from a full re-read of positions
        [0, filled) — the rollback path (speculative decode overwrites
        rejected positions, which breaks the append-only stream)."""
        self._crc[blk] = (filled, [zlib.crc32(raw) for raw in leaf_bytes])

    def crc_clone(self, src: int, dst: int) -> None:
        """Copy-on-write bookkeeping: ``dst`` now holds byte-identical
        content to ``src``'s written prefix."""
        ent = self._crc.get(src)
        if ent is not None:
            self._crc[dst] = (ent[0], list(ent[1]))
        else:
            self._crc.pop(dst, None)

    def crc_check(self, blk: int, leaf_bytes: Sequence[bytes]) -> bool:
        """Verify a re-read of ``blk``'s written prefix (positions
        [0, crc_filled)) against the ledger. A block never written
        checks clean."""
        ent = self._crc.get(blk)
        if ent is None:
            return True
        return len(ent[1]) == len(leaf_bytes) and all(
            zlib.crc32(raw) == c for raw, c in zip(leaf_bytes, ent[1]))


class PagedKVCache:
    """Per-batcher paged sequence accounting over a :class:`BlockPool`.

    Rows are decode-batch positions (the executor's fixed
    ``max_batch``); each live row owns an ordered block list. Blocks
    are allocated LAZILY as the sequence grows, but admission RESERVES
    the row's worst-case block budget up front
    (``prompt + max_new_tokens [+ speculative margin]``), so a running
    sequence can never hit an empty pool mid-decode: the admission gate
    (`can_admit`) only opens when free + evictable blocks cover every
    outstanding reservation plus the newcomer. Peak bytes resident
    still track blocks actually allocated — tokens, not rows x
    max_len.

    ``evictor`` (set by the batcher) is asked to release prefix-cache
    blocks when the free list runs dry; with the reservation invariant
    it must always be able to satisfy a reserved append.
    """

    def __init__(self, num_rows: int, blocks_per_seq: int,
                 pool: BlockPool):
        if num_rows < 1:
            raise ValueError(f"num_rows must be >= 1; got {num_rows}")
        self.num_rows = num_rows
        self.blocks_per_seq = blocks_per_seq
        self.pool = pool
        self.block_size = pool.block_size
        self._free_rows: List[int] = list(range(num_rows))[::-1]
        self.blocks: Dict[int, List[int]] = {}
        #: per-row outstanding new-block reservation (worst case growth)
        self.reserved: Dict[int, int] = {}
        self.lengths = np.zeros(num_rows, dtype=np.int32)
        self.active = np.zeros(num_rows, dtype=bool)
        self.generation = np.zeros(num_rows, dtype=np.int64)
        self.allocs = 0
        self.frees = 0
        self.peak_live = 0
        #: batcher-installed hook: evict(n) -> blocks actually released
        #: from the prefix cache back to the pool
        self.evictor: Optional[Callable[[int], int]] = None
        #: batcher-installed hook: evictable() -> prefix-cache blocks
        #: releasable on demand (refcount held only by the cache)
        self.evictable: Optional[Callable[[], int]] = None

    # -- admission capacity (the free-BLOCK signal) --------------------------
    def blocks_needed(self, tokens: int) -> int:
        return -(-max(int(tokens), 1) // self.block_size)

    def reserved_total(self) -> int:
        return sum(self.reserved.values())

    def available_blocks(self, evictable: Optional[int] = None) -> int:
        """Free + evictable - reserved. Pass ``evictable`` to reuse a
        snapshot across an admission wave: the wave's own matches pin
        blocks as it plans, and the batcher charges those pins against
        the snapshot, which only ever under-admits."""
        if evictable is None:
            evictable = (self.evictable()
                         if self.evictable is not None else 0)
        return self.pool.free_count() + evictable - \
            self.reserved_total()

    def can_admit(self, new_blocks: int,
                  evictable: Optional[int] = None) -> bool:
        """True when a newcomer needing ``new_blocks`` fresh blocks fits
        without ever starving an already-admitted sequence."""
        return bool(self._free_rows) and \
            self.available_blocks(evictable) >= new_blocks

    # -- row lifecycle -------------------------------------------------------
    def alloc_row(self, reserve_blocks: int) -> Optional[int]:
        if not self._free_rows:
            return None
        row = self._free_rows.pop()
        self.active[row] = True
        self.lengths[row] = 0
        self.generation[row] += 1
        self.blocks[row] = []
        self.reserved[row] = int(reserve_blocks)
        self.allocs += 1
        self.peak_live = max(self.peak_live, self.live())
        return row

    def attach_shared(self, row: int, blk: int) -> None:
        """Append an already-referenced (shared prefix) block to the
        row's table; the caller transferred one refcount to this row."""
        self.blocks[row].append(blk)

    def append_block(self, row: int) -> int:
        """Allocate the row's next block from the pool, evicting
        prefix-cache runs when the free list is dry. Guaranteed to
        succeed for reserved growth (the admission invariant)."""
        blk = self.pool.alloc()
        if blk is None and self.evictor is not None:
            self.evictor(1)
            blk = self.pool.alloc()
        if blk is None:
            raise RuntimeError(
                "paged KV pool exhausted on a RESERVED append — the "
                "admission gate must make this unreachable")
        self.blocks[row].append(blk)
        if self.reserved.get(row, 0) > 0:
            self.reserved[row] -= 1
        return blk

    def ensure(self, row: int, tokens: int) -> List[int]:
        """Grow the row's table to cover ``tokens`` virtual positions;
        returns the pool indices of any newly allocated blocks."""
        fresh = []
        while len(self.blocks[row]) * self.block_size < tokens:
            fresh.append(self.append_block(row))
        return fresh

    def free_row(self, row: int) -> None:
        """Release the row and every block reference it holds — shared
        prefix blocks survive under the prefix cache's own refcount.
        MUST run in the same scheduling iteration the sequence retires
        (deadline-expired and shed sequences included): a leaked block
        reference is capacity gone forever."""
        if not self.active[row]:
            raise ValueError(f"row {row} is not live")
        for blk in self.blocks.pop(row, []):
            self.pool.decref(blk)
        self.reserved.pop(row, None)
        self.active[row] = False
        self.lengths[row] = 0
        self._free_rows.append(row)
        self.frees += 1

    # -- views ---------------------------------------------------------------
    def table(self) -> np.ndarray:
        """The `[num_rows, blocks_per_seq]` int32 block-table matrix the
        executor step consumes; -1 marks unassigned entries."""
        t = np.full((self.num_rows, self.blocks_per_seq), -1, np.int32)
        for row, blks in self.blocks.items():
            t[row, :len(blks)] = blks
        return t

    def live(self) -> int:
        return self.num_rows - len(self._free_rows)

    def occupancy(self) -> float:
        """Blocks in use / pool size — the token-resident occupancy the
        block-occupancy gauge exports (NOT a row count: rows are free,
        memory is not)."""
        return self.pool.occupancy()
