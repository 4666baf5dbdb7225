from .transformer import DeepSpeedTransformerConfig, DeepSpeedTransformerLayer
from .flash_attention import (flash_attention, flash_attention_window,
                              sparse_flash_attention,
                              attention_reference, sparse_attention_reference)
from .paged_attention import paged_attention
