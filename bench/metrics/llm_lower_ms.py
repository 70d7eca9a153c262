"""LLM lowering (``repro.models.lower``: one ``lower_llm`` call, inside
each of the query's two graph expansions), per query answered in the
window, ms."""
from bench.metrics._program import window_ms


def read(run):
    return window_ms(run, "repro.models.lower")
