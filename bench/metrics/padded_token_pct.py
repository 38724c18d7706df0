"""padded_token_pct: share of the tokens the device tiers executed in the
window that were padding, from the backends' ``real_tokens`` and
``padded_tokens`` counters."""


def read(run):
    a, b = run.counters["start"], run.counters["end"]
    real = pad = 0
    for tier, c in b.items():
        if isinstance(c, dict) and "padded_tokens" in c:
            real += c["real_tokens"] - a[tier]["real_tokens"]
            pad += c["padded_tokens"] - a[tier]["padded_tokens"]
    return 100.0 * pad / (real + pad) if real + pad else None
