"""Device time of the engine's prefill program per real prompt token, in
us: the prefill module events (``jit_prefill``) summed, over the prompt
tokens of the traced job. Bucket padding is not a token."""


def read(r):
    runs = r.trace.module_seconds("jit_prefill")
    tokens = r.info.get("prompt_tokens", 0)
    return None if not runs or tokens <= 0 else 1e6 * sum(runs) / tokens
