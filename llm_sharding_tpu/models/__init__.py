from . import cache, config, deepseek_v3, gpt2, jamba, llama, longcat_flash, mimo_v2, nemotron_h, solar_open2, stack  # noqa: F401
