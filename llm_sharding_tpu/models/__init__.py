from . import cache, config, deepseek_v3, gpt2, jamba, llama, mimo_v2, nemotron_h, stack  # noqa: F401
