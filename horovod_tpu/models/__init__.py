"""Model families: image classifiers (ResNet, VGG, Inception V3, ViT) —
the reference's headline benchmark trio plus ViT — and language models
(GPT dense, MoE expert-parallel, Llama, and the served RoutedLM: dropless
routed experts with window and full attention layers mixed). All
flax/linen; float32 params with bfloat16 compute, built for dp/tp/sp/ep
meshes, except RoutedLM, whose params take the dtype its config names."""
from .resnet import ResNet18, ResNet50          # noqa: F401
from .vgg import VGG, VGG16, VGG19              # noqa: F401
from .inception import InceptionV3              # noqa: F401
from .gpt import GPT, GPTConfig                 # noqa: F401
from .vit import (                              # noqa: F401
    ViT, ViTConfig, ViT_S, ViT_B, ViT_Tiny, vit_partition_rules,
)
from .moe import (                              # noqa: F401
    MoEGPT, MoEGPTConfig, moe_partition_rules, moe_aux_loss,
)
from .llama import (                            # noqa: F401
    Llama, LlamaConfig, Llama_1B, llama_partition_rules,
)
from .routed_lm import RoutedLM, RoutedLMConfig  # noqa: F401
from .gpt_pp import gpt_pp_init, make_gpt_pp_step   # noqa: F401
