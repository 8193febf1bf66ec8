"""Prefix cache: prompt tokens served from the radix trie over prompt
tokens looked up, over the window (program counters).  Moves
``ttft_p50_ms``."""


def read(run):
    looked = run.counters.get("prompt_tokens", 0)
    if looked <= 0:
        return None
    return 100.0 * run.counters["hit_tokens"] / looked
