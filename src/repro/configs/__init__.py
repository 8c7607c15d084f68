from .base import (AttnConfig, Block, EncoderConfig, InputShape,
                   INPUT_SHAPES, MLAConfig, ModelConfig, MoEConfig,
                   SSMConfig, Stage, reduced)
from .registry import ALIASES, ARCH_IDS, all_configs, get_config
