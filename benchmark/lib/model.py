"""From a configuration file to the program's model object: the published
keys are passed as overrides of a family constructor, found by its name in
``deepspeed_tpu.models``. No preset is added to the program."""


def model_config(config_file: dict, dtype):
    """The program's ``TransformerConfig`` for a configuration file."""
    import deepspeed_tpu.models as models

    constructor = getattr(models, config_file["family"])
    overrides = {field: config_file[key] for key, field in config_file["fields"].items()}
    overrides.update(config_file.get("overrides", {}))
    return constructor(config_file["family_size"], dtype=dtype, **overrides)


def seed_word(seed: int):
    """``--seed`` may exceed 32 signed bits; JAX's ``fold_in`` takes 32."""
    import numpy as np

    return np.uint32(int(seed) & 0xFFFFFFFF)
