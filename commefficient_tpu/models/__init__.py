"""The architectures a configuration file's `model` block can name
(`gpt2_train.py --model_config`), by its `model_type`."""

import importlib

# model_type -> (module of this package, configuration class, model class)
FAMILIES = {
    "qwen3_next": ("qwen3_next", "Qwen3NextConfig", "Qwen3NextLM"),
    "glm4_moe_lite": ("glm4_moe_lite", "Glm4MoeLiteConfig", "Glm4MoeLiteLM"),
    "lfm2_moe": ("lfm2_moe", "Lfm2MoeConfig", "Lfm2MoeLM"),
}


def from_model_block(block: dict):
    """(configuration, flax model) of the family the block's `model_type`
    names; any other is an error that names those there are."""
    family = FAMILIES.get(block.get("model_type"))
    if family is None:
        raise ValueError(f"model_type {block.get('model_type')!r} is not one of "
                         f"{', '.join(sorted(FAMILIES))}")
    module = importlib.import_module(f"{__name__}.{family[0]}")
    cfg = getattr(module, family[1]).from_model_block(block)
    return cfg, getattr(module, family[2])(cfg)
