"""The GPT-2 family: everything the benchmark knows of this architecture.

A configuration file names its family (``"family": "gpt2"``) and
`manifest.Cell.family()` leads that name here, as a traffic file's
``runner`` leads to ``chipbench/runners/<name>.py``. The runners, the
per-layer readers and the tests ask this module, and nothing else, for
whatever depends on the architecture; ``chipbench/README.md`` lists the
names a family module has. Another architecture brings a file of its own
beside this one and edits nothing.

GPT-2's parts already sit in three modules, which this one names:
`chipbench.reference` (the plain reference, imports nothing of the
program), `chipbench.gpt_layout` (the reference's weights as
`models/gpt.py`'s tree, and the model objects) and `chipbench.flops`
(required operations and bytes).
"""
from chipbench import flops
from chipbench.flops import (attention_work, decode_attention_work,  # noqa: F401
                             decode_query_pattern, param_count,
                             train_flops_per_token)
from chipbench.flops import serve_steps_flops as serve_flops  # noqa: F401
from chipbench.gpt_layout import leaf_norms as program_leaf_norms  # noqa: F401
from chipbench.gpt_layout import (program_params, serve_model,  # noqa: F401
                                  train_model)
from chipbench.reference import leaf_norms as reference_leaf_norms  # noqa: F401
from chipbench.reference import make_weights as reference_weights  # noqa: F401
from chipbench.reference import Shape, Trainer, logits_at, seed_key  # noqa: F401

#: the nearest precision below the one the configurations state
#: (bfloat16 compute): the reference in it, put in the program's place,
#: has to come out as not correct
CONTROL = "fp8"

#: `--rehearse` widths: every configuration of the family shrinks to
#: these (CPU, interpret-mode kernels); nothing a rehearsal prints is a
#: measurement
REHEARSE_CONFIG = {"n_embd": 64, "n_layer": 2, "n_head": 4,
                   "n_positions": 64, "n_ctx": 64, "vocab_size": 500,
                   "assumed": {"padded_vocab_size": 512}}

#: the attributes of `Shape` a configuration file's ``published`` group
#: states by hand from the source's config.json
PUBLISHED_WIDTHS = ("d", "layers", "heads", "head_dim", "ffn", "positions",
                    "vocab")

#: the work counts a configuration file's ``hand_worked`` group gives a
#: hand-worked value for, each at the sizes in its name
WORK_COUNTS = {
    "layer_matmul_params": flops.layer_matmul_params,
    "param_count": flops.param_count,
    "train_flops_per_token_1024":
        lambda shape: flops.train_flops_per_token(shape, 1024),
    "flash_8x1024": lambda shape: flops.flash_attention_work(shape, 8, 1024),
    "paged_2000": lambda shape: flops.paged_decode_work(shape, 2000),
}
