"""Fused Pallas serving kernels: paged decode attention + on-device sampling.

The serve plane's innermost loop. The XLA lowering of
`serve/kv_cache.py paged_attention` gathers every row's KV blocks into a
contiguous ``[B, blocks_per_seq * block_size, H_kv, D]`` copy and pays
full-pool masking on EVERY decode step; this module replaces that hot
path with one block-table-aware Pallas kernel that reads KV blocks *in
place* from the pool:

* grid ``(B, blocks_per_seq)`` — the block table and positions are
  scalar-prefetched into SMEM, and the K/V ``BlockSpec`` index maps read
  the table, so the Pallas pipeline itself DMAs each row's assigned
  ``[block_size, H_kv, D]`` blocks out of the HBM pool, double-buffered.
  Whole blocks only: this Mosaic refuses a DMA slice whose two minor
  dims are not tile-aligned ("Slice shape along dimension 2 must be
  aligned to tiling (8)"), which rules out lifting one head's
  ``[block_size, D]`` slab out of a token-major pool by hand.
* each step splits its block by kv head into a per-row
  ``[H_kv, L, D]`` VMEM assembly; unassigned (``-1``) entries are
  skipped by predication (their slice is zeroed so stale VMEM bytes —
  NaN bit patterns included — can never poison the masked matmul) and
  cost no fetch beyond the first, because the pipeline re-fetches only
  on an index change.
* the last step runs the attention, one kv head at a time, so GQA query
  groups share one K/V fetch and the speculative verify's ``spec_k + 1``
  draft positions share one block-table walk (the "fused verify" is the
  same kernel at ``T = spec_k + 1``). The math mirrors
  `serve.kv_cache.masked_attention` operation-for-operation (f32
  scores, divide-after-dot scale, the same ``-1e30`` additive mask,
  `jax.nn.softmax`), which is what makes the kernel BIT-EXACT against
  the XLA oracle in interpret mode — the tier-1 parity contract
  (tests/test_serve_kernels.py). That contract guards the arithmetic;
  whether the TPU compiler accepts the kernel is guarded by
  tests/test_tpu_lowering.py (Pallas lowering) and `chip_smoke.py`
  (Mosaic + a tolerance check against the oracle on the chip).

Selection is the strict-parsed ``HOROVOD_SERVE_KERNEL`` knob
(``pallas | xla | auto``), resolved ONCE at executor build
(:func:`resolve_kernel`) so the jit cache stays flat: ``auto`` picks
pallas on TPU and the XLA oracle elsewhere; an explicit ``pallas`` off
TPU runs the kernel in interpret mode (the parity/CI tier).

On-device sampling (:func:`sample_with_probs`,
:func:`speculative_accept`) lives here too: temperature / top-p with
per-request seeds threaded as ROW DATA through the executor's one
fixed-shape jitted step, plus the rejection-sampling accept rule that
keeps speculative decoding distribution-correct under non-greedy
sampling (Leviathan et al.; accept draft ``x_i`` iff
``u * q(x_i) < p(x_i)``, emit from the residual ``norm(relu(p - q))``
on the first rejection). ``temperature == 0`` rows reduce EXACTLY to
argmax accept/rollback — the bit-identical greedy special case — and
an all-greedy batch takes a `lax.cond` fast path that skips the
top-p sort entirely.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: additive mask for invalid key positions — shared constant with the
#: XLA oracle (serve/kv_cache.py _MASK_VALUE); exp(MASK - max)
#: underflows to exactly 0.0 in f32, which is what makes masked
#: positions contribute identical zeros in both implementations
MASK_VALUE = -1e30

KERNEL_CHOICES = ("auto", "pallas", "xla")


def resolve_kernel(explicit: Optional[str] = None, *,
                   config=None) -> str:
    """Resolve the serving attention kernel ONCE (executor build time).

    ``explicit`` (a model config's ``decode_kernel``) wins; otherwise
    the strict-parsed ``HOROVOD_SERVE_KERNEL`` knob decides; ``auto``
    (the default) picks ``"pallas"`` on TPU and ``"xla"`` everywhere
    else (the oracle doubles as the CPU fallback). Returns ``"pallas"``
    or ``"xla"`` — never ``"auto"`` — so every later consumer (the jit
    trace, the obs labels, the KERNEL timeline instant) sees one fixed
    choice and the jit cache stays flat.
    """
    choice = explicit
    if choice is None:
        if config is None:
            from ..core.config import Config
            config = Config.from_env()
        choice = config.serve_kernel
    if choice not in KERNEL_CHOICES:
        raise ValueError(
            f"serve kernel must be one of {KERNEL_CHOICES}; got "
            f"{choice!r}")
    if choice == "auto":
        choice = "pallas" if jax.default_backend() == "tpu" else "xla"
    return choice


# ---------------------------------------------------------------------------
# paged decode / fused-verify attention kernel
# ---------------------------------------------------------------------------

def _first_window_block(pos, window: int, BS: int):
    """Table entry that holds the oldest key a row's first query (at
    ``pos``) can see through a window of ``window`` keys."""
    return jnp.maximum(pos - window + 1, 0) // BS


def _paged_attn_kernel(tbl_ref, pos_ref, q_ref, kb_ref, vb_ref, o_ref,
                       k_scr, v_scr, *, G: int, BS: int, nblk: int,
                       window: Optional[int] = None):
    """One (row, table entry) grid step. The pipeline has already
    fetched pool block ``max(table[b, j], 0)`` into ``kb_ref``/``vb_ref``
    (``[1, BS, KV, D]``); an assigned block is split by kv head into the
    row's ``[KV, nblk * BS, D]`` VMEM assembly, an unassigned (``-1``)
    entry zero-fills its slice. The last entry's step runs the oracle's
    masked-attention math over the assembled view, one kv head at a
    time, for all ``T * G`` queries of that head (T positions x G
    grouped query heads) at once.

    With a ``window`` the grid's second axis is shorter than the table:
    step ``j`` holds table entry ``first + j``, ``first`` being the
    entry of the oldest key the row's first query can see, and the
    assembly starts at key ``first * BS``."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    KV, L, D = k_scr.shape
    dst = pl.ds(pl.multiple_of(j * BS, BS), BS)
    if window is None:
        base = 0
        blk = tbl_ref[b, j]
    else:
        first = _first_window_block(pos_ref[b], window, BS)
        base = first * BS
        entry = first + j
        # entries past the table hold no key a query can see
        blk = jnp.where(entry < nblk,
                        tbl_ref[b, jnp.minimum(entry, nblk - 1)], -1)

    @pl.when(blk >= 0)
    def _():
        for h in range(KV):
            k_scr[h, dst, :] = kb_ref[0, :, h, :]
            v_scr[h, dst, :] = vb_ref[0, :, h, :]

    @pl.when(blk < 0)
    def _():
        # unassigned entry: zero the slice so stale scratch bytes (NaN
        # bit patterns included) can never poison the 0-probability
        # value matmul (0 * NaN)
        k_scr[:, dst, :] = jnp.zeros((KV, BS, D), k_scr.dtype)
        v_scr[:, dst, :] = jnp.zeros((KV, BS, D), v_scr.dtype)

    @pl.when(j == L // BS - 1)
    def _():
        pos = pos_ref[b]
        TG = q_ref.shape[2]
        # query row r = t * G + g may see key j iff j <= pos + t, i.e.
        # (j - pos) * G <= r — the oracle's mask without a vector
        # integer division
        r_of = jax.lax.broadcasted_iota(jnp.int32, (TG, L), 0)
        j_of = jax.lax.broadcasted_iota(jnp.int32, (TG, L), 1)
        if window is None:
            valid = (j_of - pos) * G <= r_of
        else:
            # ... and iff j > pos + t - window, i.e.
            # (j - pos + window) * G > r
            rel = j_of + (base - pos)
            valid = (rel * G <= r_of) & ((rel + window) * G > r_of)

        def head(h, carry):
            q = q_ref[0, h].astype(jnp.float32)              # [T*G, D]
            kf = k_scr[h].astype(jnp.float32)                # [L, D]
            vf = v_scr[h].astype(jnp.float32)
            # divide-after-dot, exactly like the oracle's einsum / sqrt(D)
            s = jax.lax.dot_general(
                q, kf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) / np.sqrt(D)
            s = jnp.where(valid, s, MASK_VALUE)
            p = jax.nn.softmax(s, axis=-1)
            o = jax.lax.dot_general(p, vf, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            o_ref[0, h] = o.astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, KV, head, 0)


def _vmem_limit_bytes(TG: int, KV: int, D: int, BS: int, nblk: int,
                      itemsize: int) -> int:
    """Scoped-VMEM request for one program, from the shapes. Mosaic pads
    the two minor dims of every buffer to its (sublane, 128-lane) tile,
    so D=64 costs as much as D=128 and a ``[BS, KV, D]`` bf16 block with
    KV=12 occupies a full 16 x 128 tile per token. Never below the
    compiler's 16 MiB default; a request beyond the chip's VMEM is the
    compiler's error to raise."""
    def up(x, m):
        return pl.cdiv(x, m) * m

    sub = 8 * (4 // itemsize)
    lanes = up(D, 128)
    L = nblk * BS
    assembly = 2 * KV * up(L, sub) * lanes * itemsize    # k_scr, v_scr
    windows = 2 * 2 * BS * up(KV, sub) * lanes * itemsize
    windows += 2 * 2 * KV * up(TG, sub) * lanes * itemsize
    scores = 6 * up(TG, 8) * L * 4            # [T*G, L] f32 temporaries
    head = 2 * L * lanes * 4                  # one head's f32 K and V
    need = assembly + windows + scores + head
    return max(16 << 20, need + need // 4)


def window_entries(window: Optional[int], T: int, BS: int, nblk: int) -> int:
    """Table entries one row's kernel walks: all ``nblk`` without a
    window, else as many as ``T`` consecutive queries with ``window``
    visible keys each can span (``window / BS + 1`` for one query)."""
    if window is None:
        return nblk
    return min(nblk, (window + T - 2) // BS + 2)


@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def _paged_attention_call(q, pool_k, pool_v, block_tables, positions,
                          interpret: bool, window: Optional[int] = None):
    B, T, H, D = q.shape
    _NB, BS, KV, _ = pool_k.shape
    nblk = block_tables.shape[1]
    nwin = window_entries(window, T, BS, nblk)
    G = H // KV
    # [B, T, H, D] -> [B, KV, T*G, D] (row order t*G + g, the oracle's
    # flattening): every block's two minor dims then span the whole
    # array, which is what the TPU lowering requires of a block
    qf = q.reshape(B, T, KV, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, KV, T * G, D)

    def row_map(b, j, tbl, pos):
        return b, 0, 0, 0

    def blk_map(b, j, tbl, pos):
        # -1 entries fetch block 0 and the kernel ignores what arrives;
        # the pipeline re-fetches only when the index changes, so a
        # row's unassigned tail costs one block, not nblk
        if window is not None:
            # the walk starts at the row's first in-window entry
            j = jnp.minimum(_first_window_block(pos[b], window, BS) + j,
                            nblk - 1)
        return jnp.maximum(tbl[b, j], 0), 0, 0, 0

    kern = functools.partial(_paged_attn_kernel, G=G, BS=BS, nblk=nblk,
                             window=window)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,            # block tables, positions
            grid=(B, nwin),
            in_specs=[
                pl.BlockSpec((1, KV, T * G, D), row_map),
                pl.BlockSpec((1, BS, KV, D), blk_map),   # pool_k block
                pl.BlockSpec((1, BS, KV, D), blk_map),   # pool_v block
            ],
            out_specs=pl.BlockSpec((1, KV, T * G, D), row_map),
            scratch_shapes=[
                pltpu.VMEM((KV, nwin * BS, D), pool_k.dtype),
                pltpu.VMEM((KV, nwin * BS, D), pool_v.dtype),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, KV, T * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # rows are independent; a row's table walk fills the
            # assembly in order and the output block is written last
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes(
                T * G, KV, D, BS, nwin, pool_k.dtype.itemsize)),
        interpret=interpret,
    )(block_tables, positions, qf, pool_k, pool_v)
    return out.reshape(B, KV, T, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, T, H, D)


def paged_attention_fused(q: jax.Array, pool_k: jax.Array,
                          pool_v: jax.Array, block_tables: jax.Array,
                          positions: jax.Array, *,
                          interpret: Optional[bool] = None,
                          window: Optional[int] = None) -> jax.Array:
    """Drop-in fused replacement for `serve.kv_cache.paged_attention`.

    q ``[B, T, H, D]``; pool_k/pool_v ``[num_blocks, block_size, H_kv,
    D]``; block_tables ``[B, blocks_per_seq]`` int32 (-1 unassigned);
    positions ``[B]``. ``T = 1`` is the decode step; ``T = spec_k + 1``
    is the fused speculative verify (all draft positions share one
    block-table walk and one KV fetch per (row, kv head)). Output
    ``[B, T, H, D]`` — bit-exact against the oracle in interpret mode.

    ``window`` (static, per layer): a query at position ``p`` sees keys
    ``(p - window, p]`` only. The grid then covers just the table
    entries that can hold a visible key (:func:`window_entries`,
    starting at the row's first in-window block), so a window layer's
    walk and VMEM assembly do not grow with the context. ``None`` is the
    program without the argument, bit for bit.

    ``interpret=None`` auto-selects: compiled on TPU, interpret mode
    everywhere else (the CPU parity/CI tier).
    """
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1 or None; got {window}")
    if q.shape[2] % pool_k.shape[2]:
        raise ValueError(
            f"q heads {q.shape[2]} must be a multiple of kv heads "
            f"{pool_k.shape[2]}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _paged_attention_call(
        q, pool_k, pool_v, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(positions, jnp.int32), bool(interpret),
        None if window is None else int(window))


# ---------------------------------------------------------------------------
# on-device batched sampling (temperature / top-p, per-request seeds)
# ---------------------------------------------------------------------------

#: key-stream domains: one sub-stream per randomness consumer so draft
#: proposals, accept uniforms and residual draws are mutually
#: independent (the rejection-sampling correctness requirement)
STREAM_SAMPLE = 0     # plain sampling: prefill, decode, bonus/full draws
STREAM_DRAFT = 1      # draft executors' proposal draws
STREAM_ACCEPT = 2     # speculative accept uniforms
STREAM_RESIDUAL = 3   # speculative residual draws


def _row_keys(seed: jax.Array, stream: int, ctr: jax.Array) -> jax.Array:
    """Per-row PRNG keys from (request seed, stream domain, per-row
    draw counter) — independent of batch position by construction,
    which is what makes a request's token stream deterministic across
    batch placements and restarts."""
    def one(s, c):
        k = jax.random.PRNGKey(s)
        return jax.random.fold_in(jax.random.fold_in(k, stream), c)
    return jax.vmap(one)(seed.astype(jnp.uint32), ctr.astype(jnp.uint32))


def filtered_probs(logits: jax.Array, temperature: jax.Array,
                   top_p: jax.Array) -> jax.Array:
    """The sampling distribution: softmax(logits / temperature)
    restricted to the top-p nucleus and renormalized; ``[..., V]`` over
    ``[...]``-shaped per-row parameters.

    The nucleus is the smallest probability-sorted set whose mass
    reaches ``top_p`` (every token whose PRECEDING cumulative mass is
    below ``top_p`` — at least one token always survives, and
    ``top_p = 1.0`` keeps the full distribution). Ties are broken by
    the stable descending sort (lower token id first).
    ``temperature <= 0`` rows collapse to the one-hot argmax — the
    greedy distribution, which is what makes greedy a special case of
    every sampled path rather than a separate code path.
    """
    lf = logits.astype(jnp.float32)
    greedy_hot = jax.nn.one_hot(jnp.argmax(lf, axis=-1), lf.shape[-1],
                                dtype=jnp.float32)
    t = jnp.maximum(temperature, 1e-6)[..., None]
    pr = jax.nn.softmax(lf / t, axis=-1)
    order = jnp.argsort(-pr, axis=-1, stable=True)
    sp = jnp.take_along_axis(pr, order, axis=-1)
    cum = jnp.cumsum(sp, axis=-1)
    keep_sorted = (cum - sp) < top_p[..., None]
    inv = jnp.argsort(order, axis=-1, stable=True)
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    f = jnp.where(keep, pr, 0.0)
    f = f / jnp.sum(f, axis=-1, keepdims=True)
    return jnp.where((temperature <= 0)[..., None], greedy_hot, f)


def _categorical(keys: jax.Array, probs: jax.Array) -> jax.Array:
    """Row-wise categorical draw from explicit probabilities (zeros
    are unreachable: log(0) = -inf)."""
    return jax.vmap(
        lambda k, p: jax.random.categorical(k, jnp.log(p)))(
            keys, probs).astype(jnp.int32)


def sample_with_probs(logits: jax.Array, temperature: jax.Array,
                      top_p: jax.Array, seed: jax.Array,
                      ctr: jax.Array, *, stream: int = STREAM_SAMPLE
                      ) -> Tuple[jax.Array, jax.Array]:
    """Sample one token per row from ``logits [B, V]``; returns
    ``(tokens [B] int32, probs [B, V])`` where ``probs`` is the exact
    filtered distribution each token was drawn from (what a draft
    executor hands the verify step as ``q``).

    An all-greedy batch takes a `lax.cond` fast path — pure argmax, no
    top-p sort — inside the SAME compiled program, so greedy traffic
    never pays the sampling machinery and the jit cache stays flat.
    Greedy rows inside a mixed batch produce the identical argmax
    token either way.
    """
    gre = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)

    def greedy_path(_):
        return gre, jax.nn.one_hot(gre, logits.shape[-1],
                                   dtype=jnp.float32)

    def sampled_path(_):
        pr = filtered_probs(logits, temperature, top_p)
        tok = _categorical(_row_keys(seed, stream, ctr), pr)
        return jnp.where(temperature <= 0, gre, tok), pr

    return jax.lax.cond(jnp.any(temperature > 0), sampled_path,
                        greedy_path, None)


def speculative_accept(tokens: jax.Array, draft_probs: jax.Array,
                       logits: jax.Array, n_draft: jax.Array,
                       temperature: jax.Array, top_p: jax.Array,
                       seed: jax.Array, ctr: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """The rejection-sampling accept rule, fused into the verify step.

    tokens ``[B, k+1]`` (column 0 = each row's last emitted token,
    columns 1.. = the draft proposals); draft_probs ``[B, k, V]`` (the
    exact filtered distribution each proposal was drawn from);
    logits ``[B, k+1, V]`` (the target's verify logits, position i
    scoring the token AFTER tokens[:, i]); n_draft ``[B]`` (how many
    proposals each row really has — rows mid-resync draft fewer than
    k). Returns ``(emitted [B, k+1] int32, n_accept [B] int32)``:
    row r's emitted tokens are ``emitted[r, :n_accept[r] + 1]``.

    Draft ``i`` is accepted iff ``u_i * q_i(x_i) < p_i(x_i)``; the
    first rejection emits a draw from the residual
    ``norm(relu(p_i - q_i))``, and a row that accepted every real
    draft emits a full draw from ``p_{n_draft}`` (the bonus token).
    With ``temperature == 0`` both distributions are one-hot and the
    rule reduces EXACTLY to argmax accept/rollback — bit-identical
    greedy speculative decoding; an all-greedy batch short-circuits
    through a sort-free `lax.cond` branch of the same program.
    """
    B, K1, V = logits.shape
    k = K1 - 1
    drafts = tokens[:, 1:]
    iot = jnp.arange(k)[None, :]
    has_draft = iot < n_draft[:, None]

    def greedy_path(_):
        preds = jnp.argmax(logits.astype(jnp.float32),
                           axis=-1).astype(jnp.int32)       # [B, k+1]
        acc = (drafts == preds[:, :k]) & has_draft
        n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                        axis=1)
        fix = jnp.take_along_axis(preds, n_acc[:, None], axis=1)[:, 0]
        return _assemble(drafts, fix, n_acc, k)

    def sampled_path(_):
        p = filtered_probs(logits, temperature[:, None],
                           jnp.broadcast_to(top_p[:, None], (B, K1)))
        q = draft_probs.astype(jnp.float32)
        p_tok = jnp.take_along_axis(
            p[:, :k], drafts[..., None], axis=-1)[..., 0]
        q_tok = jnp.take_along_axis(
            q, drafts[..., None], axis=-1)[..., 0]
        ctr_i = ctr[:, None] + iot                           # [B, k]
        seed_i = jnp.broadcast_to(seed[:, None], (B, k))
        ukeys = _row_keys(seed_i.reshape(-1), STREAM_ACCEPT,
                          ctr_i.reshape(-1))
        u = jax.vmap(jax.random.uniform)(ukeys).reshape(B, k)
        acc = (u * q_tok < p_tok) & has_draft
        n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                        axis=1)
        # residual draw per draft position (gathered at the first
        # rejection); rows with p == q never reach theirs, the
        # fallback only keeps the math NaN-free
        res_un = jnp.maximum(p[:, :k] - q, 0.0)
        res_sum = jnp.sum(res_un, axis=-1, keepdims=True)
        res = jnp.where(res_sum > 0, res_un / jnp.maximum(res_sum, 1e-20),
                        p[:, :k])
        rkeys = _row_keys(seed_i.reshape(-1), STREAM_RESIDUAL,
                          ctr_i.reshape(-1))
        res_tok = _categorical(rkeys, res.reshape(B * k, V)).reshape(B, k)
        # full draw per verify position (the bonus token when every
        # real draft was accepted — position n_draft has no draft to
        # reject, so the emit there is a plain sample from p)
        ctr_f = ctr[:, None] + jnp.arange(K1)[None, :]
        seed_f = jnp.broadcast_to(seed[:, None], (B, K1))
        fkeys = _row_keys(seed_f.reshape(-1), STREAM_SAMPLE,
                          ctr_f.reshape(-1))
        full_tok = _categorical(fkeys, p.reshape(B * K1, V)).reshape(B, K1)
        # greedy rows: every draw above collapses to the argmax
        preds = jnp.argmax(logits.astype(jnp.float32),
                           axis=-1).astype(jnp.int32)
        g = (temperature <= 0)[:, None]
        res_tok = jnp.where(g, preds[:, :k], res_tok)
        full_tok = jnp.where(g, preds, full_tok)
        fix_pool = jnp.concatenate(
            [jnp.where(n_acc[:, None] < n_draft[:, None],
                       res_tok, full_tok[:, :k]),
             full_tok[:, k:]], axis=1)                       # [B, k+1]
        fix = jnp.take_along_axis(fix_pool, n_acc[:, None], axis=1)[:, 0]
        return _assemble(drafts, fix, n_acc, k)

    return jax.lax.cond(jnp.any(temperature > 0), sampled_path,
                        greedy_path, None)


def _assemble(drafts: jax.Array, fix: jax.Array, n_acc: jax.Array,
              k: int) -> Tuple[jax.Array, jax.Array]:
    """[accepted drafts..., fix token, zero padding] per row."""
    iot = jnp.arange(k + 1)[None, :]
    drafts_pad = jnp.concatenate(
        [drafts, jnp.zeros_like(fix)[:, None]], axis=1)
    emitted = jnp.where(
        iot < n_acc[:, None], drafts_pad,
        jnp.where(iot == n_acc[:, None], fix[:, None], 0))
    return emitted.astype(jnp.int32), n_acc.astype(jnp.int32)
