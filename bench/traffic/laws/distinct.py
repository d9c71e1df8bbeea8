"""Prompt law ``distinct``: every prompt drawn on its own from the mix's
token law, none sharing a prefix with another.

    {"law": "distinct"}
"""


def prompts(part: dict, lengths, stream, ids) -> list:
    """``stream(*words)`` is the seed's generator for those words and
    ``ids(rng, n)`` draws ``n`` ids from the token law."""
    return [ids(stream(3, i), int(n)) for i, n in enumerate(lengths)]
