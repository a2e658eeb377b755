"""The training system under test: ``deepspeed_tpu.initialize`` over the
configuration's model on a ``data=<chips>`` mesh, driven through
``engine.train_batch``. The batch geometry comes from the traffic file."""

from types import SimpleNamespace


def build(cell: dict, seed: int, devices, rehearsal: bool, phases):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.parallel.mesh import BATCH_AXES

    from benchmark.lib.model import model_config, seed_word

    cf, tf = cell["config_file"], cell["traffic_file"]
    n = len(devices)
    seq = int(tf["seq_len"])
    sequences = int(tf["global_batch_tokens"]) // seq
    micro = int(tf["micro_batch_per_chip"])
    gas = sequences // (micro * n)
    if gas < 1 or gas * micro * n != sequences:
        raise ValueError(f"global batch of {sequences} sequences is not micro {micro} x gas x {n} chips")
    cfg = model_config(cf, jnp.float32 if rehearsal else jnp.bfloat16)
    model = TransformerLM(cfg)
    word = seed_word(seed)
    # the engine draws the weights on the device inside its own jitted,
    # sharded init; the seed is folded into the key it passes
    model.init = lambda rng, example_batch=None: init_params(cfg, jax.random.fold_in(rng, word))
    config = {"train_batch_size": sequences, "train_micro_batch_size_per_gpu": micro,
              "gradient_accumulation_steps": gas, "steps_per_print": 10**9,
              "tpu": {"mesh": {"data": n}}, **cf["engine"]}
    if rehearsal:
        config["bf16"] = {"enabled": False}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)

    batch_sharding = NamedSharding(engine.mesh, P(None, BATCH_AXES, None))
    rows_sharding = NamedSharding(engine.mesh, P(BATCH_AXES, None))
    key = jax.random.fold_in(jax.random.PRNGKey(1), word)

    draw = jax.jit(lambda k: jax.random.randint(k, (gas, micro * n, seq), 0, cfg.vocab_size, jnp.int32),
                   out_shardings=batch_sharding)

    def make_batch(index: int):
        """Batch ``index`` of the seed, made on the device as the step takes it:
        ``[gas, micro x chips, seq]`` with the rows over the data axes."""
        return draw(jax.random.fold_in(key, index))

    return SimpleNamespace(engine=engine, cfg=cfg, model=model, n=n, seq=seq, sequences=sequences,
                           micro=micro, gas=gas, tokens_per_step=sequences * seq, make_batch=make_batch,
                           batch_sharding=batch_sharding, rows_sharding=rows_sharding, key=key)
