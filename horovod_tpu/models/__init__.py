"""Model zoo for benchmarks and examples (the reference ships models inside
examples/ + tf_cnn_benchmarks; here they are a first-class subpackage)."""

from .resnet import ResNet, ResNet18, ResNet34, ResNet50, ResNet101, ResNet152  # noqa: F401
from .mlp import MLP, ConvNet  # noqa: F401
from .gdn import GDNDims, GDNMixer  # noqa: F401
from .kda import KDADims, KDAMixer  # noqa: F401
from .mamba import Mamba2Dims, Mamba2Mixer  # noqa: F401
from .moe import (BIAS_COLLECTION, MoEMLP, aux_losses, ep_param_specs,  # noqa: F401
                  expert_counts)
from .pipeline_lm import (  # noqa: F401
    merge_lm_params,
    pipeline_lm_logits,
    pipeline_lm_loss_and_grads,
    split_lm_params,
)
from .short_conv import ShortConvDims, ShortConvMixer  # noqa: F401
from .transformer import (LatentDims, RotaryScheme, SparseDims,  # noqa: F401
                          TransformerLM, align_losses)
from .vgg import VGG, VGG16, VGG19  # noqa: F401
from .inception import InceptionV3  # noqa: F401
