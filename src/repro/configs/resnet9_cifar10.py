"""ResNet-9 on CIFAR-10 — the paper's own image-classification model (§VI).

Nine conv layers + BN + ReLU, two residual blocks, global pooling, FC head;
6,573,130 parameters at full width (``model.num_params()``). ``d_model`` doubles as the base channel
width (64 at full size).
"""
from repro.configs.base import ModelConfig, register

CONFIG = register(
    ModelConfig(
        name="resnet9-cifar10",
        family="vision",
        num_layers=9,
        d_model=64,  # base width
        num_heads=0,
        num_kv_heads=0,
        d_ff=0,
        vocab_size=10,  # classes
        dtype="float32",
        param_dtype="float32",
        source="paper §VI / He et al. CVPR16",
    )
)
