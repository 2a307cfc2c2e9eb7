from . import cache, config, deepseek_v3, gpt2, llama, mimo_v2, stack  # noqa: F401
