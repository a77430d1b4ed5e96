"""Shared GPT/Llama train-step construction for the benchmark harnesses.

One builder used by BOTH gpt_bench.py (throughput/MFU) and
xplane_profile.py --model gpt (profiling) so the profiled program IS the
benchmarked program — divergence between the two was a review finding.
"""
from __future__ import annotations


def build_gpt_train_step(family="gpt", impl="pallas", layers=12, heads=12,
                         kv_heads=None, head_dim=64, seq=1024, batch=8,
                         vocab=50304, sp=1, attention=None,
                         logits_dtype="f32", remat=False):
    """Returns (step, params, opt, tokens, targets, n_params, mesh).

    `batch` is per-device; the global batch is batch * n_devices.
    Requires hvd.init() to have run (callers own init/platform policy).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.parallel.mesh_utils import make_mesh
    from horovod_tpu.parallel.tp import gpt_partition_rules, shard_params
    from horovod_tpu.training import make_gspmd_train_step

    n_dev = hvd.size()
    if n_dev % sp:
        raise ValueError(f"sp {sp} must divide device count {n_dev}")
    mesh = make_mesh(dp=n_dev // sp, sp=sp)
    attention = attention or ("ring" if sp > 1 else "dense")
    ldt = jnp.bfloat16 if logits_dtype == "bf16" else jnp.float32

    if family == "llama":
        from horovod_tpu.models.llama import (Llama, LlamaConfig,
                                              llama_partition_rules)
        cfg = LlamaConfig(vocab_size=vocab, num_layers=layers,
                          num_heads=heads, num_kv_heads=kv_heads,
                          head_dim=head_dim, max_seq_len=seq, mesh=mesh,
                          attention=attention, attention_impl=impl,
                          logits_dtype=ldt)
        model, rules = Llama(cfg), llama_partition_rules()
    else:
        from horovod_tpu.models.gpt import GPT, GPTConfig
        cfg = GPTConfig(vocab_size=vocab, num_layers=layers,
                        num_heads=heads, head_dim=head_dim,
                        max_seq_len=seq, mesh=mesh, attention=attention,
                        attention_impl=impl, remat=remat,
                        logits_dtype=ldt)
        model, rules = GPT(cfg), gpt_partition_rules()

    B = batch * n_dev
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, vocab, (B, seq)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    # smallest dp-divisible slice for init (the sp shard_map needs
    # batch % dp == 0; the full batch would trace a throwaway forward
    # at benchmark scale)
    init_rows = max(1, n_dev // sp)
    params = model.init(jax.random.PRNGKey(0), tokens[:init_rows])["params"]
    n_params = sum(x.size for x in jax.tree.leaves(params))
    params = shard_params(params, mesh, rules)
    tx = optax.adamw(1e-3)
    opt = tx.init(params)
    step = make_gspmd_train_step(model.apply, tx, mesh, rules)
    return step, params, opt, tokens, targets, n_params, mesh
