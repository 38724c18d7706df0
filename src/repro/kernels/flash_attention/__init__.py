from repro.kernels.flash_attention.ops import (
    attention_ref, flash_attention, flash_attention_pallas,
    flash_attention_tokens, flash_attention_tokens_pallas)

__all__ = ["flash_attention", "flash_attention_tokens",
           "flash_attention_pallas", "flash_attention_tokens_pallas",
           "attention_ref"]
