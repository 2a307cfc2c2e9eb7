from . import cache, config, deepseek_v3, gpt2, llama, stack  # noqa: F401
