"""Output tokens that became visible in the window ÷ the window, tokens/s."""
from benchmark import samples


def read(rec):
    return samples.tokens_in_window(rec) / rec["seconds"]
