"""Model zoo: GPT-2 family (flagship), BERT encoder, MoE GPT, GPT-J/NeoX,
Jamba (Mamba + attention hybrid), Ouro (one stack of layers looped),
DeepSeek-V2 (latent attention, routed and shared experts), AFMoE (window and
global attention layers, gated grouped-query attention, sigmoid routing),
Nemotron-H (Mamba-2 mixers, attention layers and non-gated expert layers, one
of them a layer), Phi4Flash (a decoder-hybrid-decoder: Mamba-1 and window
layers, one full layer whose K/V the cross-attention layers read again, Gated
Memory Units, differential attention), LongCat-Flash (a shortcut-connected
double block: two latent-attention sub-layers, two dense FFNs and one expert
layer that joins late; a router wider than its experts, the rest identity
experts), Qwen3-Next (Gated DeltaNet layers, whose delta-rule state is read
before it is written, and gated attention layers at a partial rotary, every
layer followed by many small experts and a gated shared one), EvaByte (a
byte-level decoder with EVA attention: a window of exact K/V rows that is
folded, a chunk to a summary row, at every window's end; eight prediction
heads)."""

from .gpt2 import GPT2, GPT2Config, PRESETS as GPT2_PRESETS


def build(name, **overrides):
    """Model factory by preset name."""
    try:
        if name.startswith("gpt2-moe"):
            from .gpt2_moe import GPT2MoE
            return GPT2MoE(preset=name, **overrides)
        if name in GPT2_PRESETS:
            return GPT2(preset=name, **overrides)
        if name.startswith("bert"):
            from .bert import Bert
            return Bert(preset=name, **overrides)
        if name.startswith("gptj"):
            from .gptj import GPTJ
            return GPTJ(preset=name, **overrides)
        if name.startswith("gptneox"):
            from .gptj import GPTNeoX
            return GPTNeoX(preset=name, **overrides)
        if name.startswith("jamba"):
            from .jamba import Jamba
            return Jamba(preset=name, **overrides)
        if name.startswith("ouro"):
            from .ouro import Ouro
            return Ouro(preset=name, **overrides)
        if name.startswith("deepseek-v2"):
            from .deepseek_v2 import DeepseekV2
            return DeepseekV2(preset=name, **overrides)
        if name.startswith("afmoe"):
            from .afmoe import Afmoe
            return Afmoe(preset=name, **overrides)
        if name.startswith("nemotron-h"):
            from .nemotron_h import NemotronH
            return NemotronH(preset=name, **overrides)
        if name.startswith("phi4flash"):
            from .phi4flash import Phi4Flash
            return Phi4Flash(preset=name, **overrides)
        if name.startswith("longcat-flash"):
            from .longcat_flash import LongcatFlash
            return LongcatFlash(preset=name, **overrides)
        if name.startswith("qwen3-next"):
            from .qwen3_next import Qwen3Next
            return Qwen3Next(preset=name, **overrides)
        if name.startswith("evabyte"):
            from .evabyte import EvaByte
            return EvaByte(preset=name, **overrides)
        if name.startswith("cifar"):
            from .cifar import CifarCNN
            return CifarCNN(preset=name, **overrides)
    except KeyError as e:
        raise ValueError(f"Unknown preset {name!r} for its model family") from e
    except ImportError as e:
        raise ValueError(f"Model family for {name!r} is not available: {e}") from e
    raise ValueError(f"Unknown model preset {name!r}; GPT-2 presets: "
                     f"{sorted(GPT2_PRESETS)}")
