"""Block-sparse attention over the paged pool: which cached blocks a
query reads, and the attention over just those.

A layer of this kind (InfLLM-v2 / MiniCPM4) keeps, beside K and V, four
COMPRESSED keys a pool block: window ``j`` is the mean of keys
``[stride*j, stride*j + 2*stride)``, ``stride`` a quarter of the block,
so window ``4b + 3`` ends a quarter into block ``b + 1`` and is stored
with block ``b``. A query at position ``p`` (context ``p + 1``):

* ``p + 1 <= dense_len``: attends to every key ``<= p``;
* else scores the compressed keys it can see (``stride*j + 2*stride - 1
  <= p``), per query head a softmax over them, summed over the heads of
  a KV group; a block's score is the largest over the five windows that
  overlap it (``4b - 1 .. 4b + 3``); it attends to the first
  ``init_blocks``, the newest ``window_blocks`` and the best-scored of
  the rest, ``topk`` blocks in all, one set a KV group. Ties go to the
  lower block (``lax.top_k`` is stable). Scores and the top-k are
  float32 at full precision: a flipped block is the one error rounding
  can make that is not small.

What is here: `write_compressed_keys` (the per-block leaf, written as the
token that completes a window arrives), `select_blocks`,
`attended_tables` (a decode step: per (row, KV group) the table of the
blocks it attends — all its blocks while dense, the ``topk`` selected
once sparse, ascending, the current block last — and the matching
length, which `ops/pallas_paged.py`'s kernel and its oracle take as
they take any table: a sparse row's whole K and V are never read),
and `prefill_attention` (many queries a row, the same rule per query,
by chunks of queries and keys in XLA with the unselected (query, block)
pairs masked and the key chunks past the diagonal skipped; a kernel
that also skips what it masks is not here yet).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_MASKED = -1e30
#: a forced block's score: above any sum of softmax weights
_FORCED = 1e30


class SparseSizes(NamedTuple):
    """The selection's sizes, in tokens and blocks."""
    block: int          # tokens a pool block holds = the selection block
    stride: int         # a compressed key every `stride` keys
    init_blocks: int
    window_blocks: int
    topk: int           # blocks attended once sparse, forced ones counted
    dense_len: int      # contexts up to this attend to everything

    @property
    def kernel(self) -> int:
        return 2 * self.stride

    @property
    def table_width(self) -> int:
        """Entries of an attended table: a dense row's blocks, or the
        selected ones."""
        return max(self.dense_len // self.block, self.topk)

    def validate(self) -> "SparseSizes":
        if self.block != 4 * self.stride:
            raise ValueError(
                f"a block of {self.block} keys holds four compressed keys: "
                f"the stride must be {self.block // 4}, got {self.stride}")
        if self.dense_len % self.block or \
                self.dense_len < self.topk * self.block:
            raise ValueError(
                f"dense_len {self.dense_len} must be whole blocks of "
                f"{self.block} and hold at least topk={self.topk} of them")
        if not 1 <= self.init_blocks + self.window_blocks <= self.topk:
            raise ValueError(
                f"init_blocks {self.init_blocks} + window_blocks "
                f"{self.window_blocks} must be within topk={self.topk}")
        return self


def write_compressed_keys(cpool: jax.Array, pool_k: jax.Array,
                          block_tables: jax.Array, positions: jax.Array,
                          update_mask: jax.Array, T: int,
                          sizes: SparseSizes) -> jax.Array:
    """The compressed keys of the windows that tokens ``[positions,
    positions + T)`` of each row complete, read back from ``pool_k``
    (already holding those tokens) and written to ``cpool``
    ``[pool_blocks, 4, H_kv, D]`` float32 through the tables. A window
    whose last key is bucket padding is written too and written again
    when the real token arrives; nothing reads it before."""
    NB, BS = pool_k.shape[0], pool_k.shape[1]
    s, kern = sizes.stride, sizes.kernel
    nblk = block_tables.shape[1]
    n_win = -(-T // s)
    # the first window whose last key is at or after `positions`
    j = ((positions - kern + s) // s)[:, None] + jnp.arange(n_win)[None]
    ok = update_mask[:, None] & (j >= 0) & \
        (s * j + kern - 1 < (positions + T)[:, None])
    key_pos = jnp.maximum(s * j, 0)[..., None] + jnp.arange(kern)  # [B,W,K]
    blk = jnp.take_along_axis(
        block_tables, jnp.minimum(key_pos // BS, nblk - 1).reshape(
            key_pos.shape[0], -1), axis=1).reshape(key_pos.shape)
    keys = pool_k[jnp.maximum(blk, 0), key_pos % BS]    # [B, W, K, KV, D]
    c = jnp.mean(keys.astype(jnp.float32), axis=2)
    home = jnp.take_along_axis(
        block_tables, jnp.clip(j // 4, 0, nblk - 1), axis=1)
    ok &= (home >= 0) & (j // 4 < nblk)
    flat = jnp.where(ok, home * 4 + j % 4, NB * 4).reshape(-1)
    out = cpool.reshape(NB * 4, *cpool.shape[2:]).at[flat].set(
        c.reshape(-1, *c.shape[2:]), mode="drop")
    return out.reshape(cpool.shape)


def block_scores(q: jax.Array, ckeys: jax.Array, q_pos: jax.Array,
                 sizes: SparseSizes) -> jax.Array:
    """q ``[Q, H_kv, G, D]`` (queries of ONE row), ckeys
    ``[n_windows, H_kv, D]`` float32 (the row's, through its table),
    q_pos ``[Q]`` -> block scores ``[H_kv, Q, n_blocks]`` float32."""
    D = q.shape[-1]
    n_win = ckeys.shape[0]
    s = jnp.einsum("qkgd,nkd->kgqn", q.astype(jnp.float32), ckeys,
                   precision=HIGHEST) / math.sqrt(D)
    seen = (sizes.stride * jnp.arange(n_win) + sizes.kernel - 1)[None, :] \
        <= q_pos[:, None]                                       # [Q, n]
    r = jax.nn.softmax(jnp.where(seen[None, None], s, _MASKED), axis=-1)
    r = jnp.sum(jnp.where(seen[None, None], r, 0.0), axis=1)    # [KV, Q, n]
    per_block = r.reshape(*r.shape[:2], n_win // 4, 4)
    # windows 4b-1 .. 4b+2: the same, one window earlier
    shifted = jnp.pad(r, ((0, 0), (0, 0), (1, 0)))[..., :n_win].reshape(
        per_block.shape)
    return jnp.maximum(shifted.max(-1), per_block[..., 3])


def select_blocks(scores: jax.Array, q_pos: jax.Array,
                  sizes: SparseSizes) -> jax.Array:
    """Block scores ``[H_kv, Q, n_blocks]``, q_pos ``[Q]`` -> the
    ``topk`` blocks each (KV group, query) attends, ascending
    ``[H_kv, Q, topk]`` int32: the first ``init_blocks``, the newest
    ``window_blocks`` up to the query's own, the best-scored of the
    rest. Means nothing for a query with fewer than ``topk`` blocks
    (a dense one)."""
    b = jnp.arange(scores.shape[-1])[None, :]
    cur = (q_pos // sizes.block)[:, None]
    forced = (b < sizes.init_blocks) | \
        ((b > cur - sizes.window_blocks) & (b <= cur))
    ranked = jnp.where(forced[None], _FORCED, scores)
    ranked = jnp.where((b > cur)[None], -1.0, ranked)
    _, idx = jax.lax.top_k(ranked, sizes.topk)
    return jnp.sort(idx, axis=-1).astype(jnp.int32)


def gather_compressed(cpool: jax.Array, table: jax.Array) -> jax.Array:
    """One row's compressed keys ``[n_blocks * 4, H_kv, D]`` through its
    table ``[n_blocks]`` (unassigned entries read block 0: no query
    sees a window past its own position)."""
    c = cpool[jnp.maximum(table, 0)]                # [nblk, 4, KV, D]
    return c.reshape(-1, *c.shape[2:])


def attended_tables(q: jax.Array, cpool: jax.Array,
                    block_tables: jax.Array, positions: jax.Array,
                    sizes: SparseSizes
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A decode step's tables: q ``[B, H, D]`` (one query a row, heads
    grouped ``H_kv x G``), cpool ``[pool_blocks, 4, H_kv, D]``,
    block_tables ``[B, n_blocks]``, positions ``[B]`` ->
    ``(tables [B, H_kv, width], lengths [B], attended [B])``: per KV
    group the pool blocks the row attends (``-1`` behind them), the
    position of the query within the keys so laid out (``lengths`` is
    what a paged kernel takes as the row's position), and how many
    blocks that is."""
    B, H, D = q.shape
    KV = cpool.shape[2]
    W, nblk = sizes.table_width, block_tables.shape[1]

    def row(qr, table, pos):
        sc = block_scores(qr.reshape(1, KV, H // KV, D),
                          gather_compressed(cpool, table), pos[None], sizes)
        return select_blocks(sc, pos[None], sizes)[:, 0]        # [KV, topk]

    idx = jax.vmap(row)(q, block_tables, positions)             # [B,KV,topk]
    chosen = jnp.take_along_axis(block_tables[:, None, :], idx, axis=2)
    chosen = jnp.pad(chosen, ((0, 0), (0, 0), (0, W - sizes.topk)),
                     constant_values=-1)
    dense = block_tables[:, :W] if nblk >= W else jnp.pad(
        block_tables, ((0, 0), (0, W - nblk)), constant_values=-1)
    sparse = positions + 1 > sizes.dense_len
    tables = jnp.where(sparse[:, None, None], chosen, dense[:, None, :])
    lengths = jnp.where(
        sparse, (sizes.topk - 1) * sizes.block + positions % sizes.block,
        positions)
    attended = jnp.where(sparse, sizes.topk, positions // sizes.block + 1)
    return tables, lengths, attended


# ---------------------------------------------------------------------------
# many queries a row: a prefill
# ---------------------------------------------------------------------------

#: queries and keys a tile of the prefill's attention holds
QUERY_TILE, KEY_TILE = 512, 1024


def _row_prefill(q, keys, vals, ckeys, pos, sizes: SparseSizes):
    """q [T, KV, G, D]; keys/vals [L, KV, D] (the row's blocks in table
    order, L whole key tiles); ckeys [L / stride, KV, D]; pos the first
    query's position -> [T, KV, G, D] in q's dtype."""
    T, KV, G, D = q.shape
    L, BS = keys.shape[0], sizes.block
    tq = min(QUERY_TILE, T)
    tk = min(KEY_TILE, L)
    pad = (-T) % tq
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    nblk = L // BS

    def tile(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * tq, tq)
        q_pos = pos + i * tq + jnp.arange(tq)

        def chosen(_):
            idx = select_blocks(block_scores(qi, ckeys, q_pos, sizes),
                                q_pos, sizes)                 # [KV,tq,topk]
            hit = jnp.any(idx[..., None] == jnp.arange(nblk), axis=-2)
            return hit | (q_pos + 1 <= sizes.dense_len)[None, :, None]

        # a tile of queries all still dense scores nothing
        sel = jax.lax.cond(q_pos[-1] + 1 > sizes.dense_len, chosen,
                           lambda _: jnp.ones((KV, tq, nblk), bool), None)

        def body(j, carry):
            o, m, l = carry
            kj = jax.lax.dynamic_slice_in_dim(keys, j * tk, tk)
            vj = jax.lax.dynamic_slice_in_dim(vals, j * tk, tk)
            s = jnp.einsum("tkgd,skd->kgts", qi, kj,
                           preferred_element_type=jnp.float32) / math.sqrt(D)
            k_pos = j * tk + jnp.arange(tk)
            ok = (k_pos[None, :] <= q_pos[:, None])[None] & jnp.repeat(
                jax.lax.dynamic_slice_in_dim(sel, j * (tk // BS),
                                             tk // BS, axis=2), BS, axis=2)
            s = jnp.where(ok[:, None], s, _MASKED)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.where(ok[:, None], jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            o = o * corr[..., None] + jnp.einsum(
                "kgts,skd->kgtd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32)
            return o, m_new, l

        o0 = jnp.zeros((KV, G, tq, D), jnp.float32)
        m0 = jnp.full((KV, G, tq), _MASKED, jnp.float32)
        # key tiles past the tile's last query hold nothing it can see
        n_tiles = jnp.minimum(q_pos[-1] // tk + 1, L // tk)
        o, _, l = jax.lax.fori_loop(0, n_tiles, body,
                                    (o0, m0, jnp.zeros_like(m0)))
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    out = jax.lax.map(tile, jnp.arange((T + pad) // tq))  # [n,KV,G,tq,D]
    return out.transpose(0, 3, 1, 2, 4).reshape(T + pad, KV, G, D)[:T]


def prefill_attention(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                      cpool: jax.Array, block_tables: jax.Array,
                      positions: jax.Array, sizes: SparseSizes) -> jax.Array:
    """Causal attention of ``T`` queries a row ``[B, T, H, D]`` from
    ``positions`` [B] over the row's cached blocks (this step's tokens
    already written), each query by the rule at the top of this file.
    A python loop over the rows: a prefill step holds one, or a few."""
    B, T, H, D = q.shape
    KV, BS = pool_k.shape[2], pool_k.shape[1]
    nblk = block_tables.shape[1]
    tk = min(KEY_TILE, nblk * BS)
    extra = (-(nblk * BS) % tk) // BS           # whole key tiles
    out = []
    for b in range(B):
        table = jnp.pad(jnp.maximum(block_tables[b], 0), (0, extra))
        keys = pool_k[table].reshape(-1, KV, D)
        vals = pool_v[table].reshape(-1, KV, D)
        o = _row_prefill(q[b].reshape(T, KV, H // KV, D), keys, vals,
                         gather_compressed(cpool, table), positions[b],
                         sizes)
        out.append(o.reshape(T, H, D))
    return jnp.stack(out)
