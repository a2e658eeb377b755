"""The serving system under test for a model of which this chip holds a SHARE
(some of the routed experts of each layer): what ``builders/serve.py`` builds
and ``builders/serve_routed.py`` decides, with three things of its own.

* **The weights are ONE model for every seed; ``--seed`` draws the token ids
  (the traffic's and the check's).** A chip's share of the routed slots
  follows the draw of the weights: with random weights the router's input has
  a large part that every token shares (the post-norm scales an attention
  output that is nearly the same at every position up to unit size), which
  lifts some experts at every token, so the 32 experts here took 13.8% of the
  slots on one seed and 14.6% on another where an eighth is 12.5, the expert
  bytes a step streams moved with it, and ``serve_tokens_per_s`` spread by
  3.5% over six seeds (PERF.md section 6, PR 31). A deployment serves one
  model; a spread over models is not what its users feel. So
  ``serve.make_params`` is called with the constant ``WEIGHTS_WORD``.
* **The draws of what only such a family has.** ``serve.make_params`` draws
  every stacked ``[L, n]`` leaf as a matrix and every other vector as one or
  zero; here every norm gain (a leaf named ``*scale``) is drawn about one,
  ``1 + 0.1 N(0, 1)``, so that a program that leaves a norm out, or applies the
  wrong one, reads as not correct; and the router's selection bias
  ``gate_bias`` (float32, trained by no gradient) is drawn small: for each
  layer and each chip's run of ``held`` experts, the ``held`` mid-quantile
  points of ``N(0, bias_std^2)`` in a random order (every chip's run holds the
  same multiset, so the bias favours no chip). ``bias_std`` (the
  configuration's ``check.bias_std``) is the gap between the k-th and the
  next score, so the bias changes the chosen set at a third to a half of the
  positions without deciding it.
* **The check's prompt goes in as the timed prompts do**, in chunks of the
  gateway's token budget (one ``put`` holds at most ``max_ragged_batch_size``
  tokens and the check's prompt is longer: it has to reach the window), then
  one position at a time through the cache. **And an upper quantile is held
  too.** ``serve_routed.decide`` holds the LOWER quartile of the per-position
  error on each side of the window to ``quantile_tol``: the rounding level of
  the positions no flipped expert touched. Here one chip's experts are an
  eighth of those routed over, so a wrong chosen set reaches a position only
  where a held expert enters or leaves it: a program that ignores the
  selection bias is wrong at a third to a half of the positions and right, to
  rounding, at the others, and a lower quartile cannot see it. The flips that
  bf16 rounding causes touch 2-6%, so the ``upper_quantile`` of each side is
  held to ``upper_tol`` as well.
"""

WEIGHTS_WORD = 31


def make_params(serve, bias_std: float):
    """``serve.make_params`` for the one model of this cell, with this
    builder's draws laid over it; the seed it is called with is not used."""
    plain = serve.make_params

    def draw(model, seed_word, dtype):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.scipy.special import ndtri

        params = plain(model, np.uint32(WEIGHTS_WORD), dtype)
        key = jax.random.fold_in(jax.random.PRNGKey(1), WEIGHTS_WORD)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        # the large leaves stay as they are: no second copy of the weights is ever alive
        leaves = [(1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, i), leaf.shape, jnp.float32)).astype(dtype)
                  if str(getattr(path[-1], "key", path[-1])).endswith("scale") else leaf
                  for i, (path, leaf) in enumerate(leaves)]
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        layers, experts = params["blocks"]["gate_bias"].shape
        held = model.config.experts_held
        points = bias_std * ndtri((jnp.arange(held, dtype=jnp.float32) + 0.5) / held)
        runs = jax.random.split(jax.random.fold_in(key, len(leaves)), layers * (experts // held))
        params["blocks"]["gate_bias"] = jax.vmap(lambda r: jax.random.permutation(r, points))(runs).reshape(
            layers, experts).astype(jnp.float32)
        return params

    return draw


def chunked_logits(chunk: int):
    """``serve.system_logits`` with the prompt fed in chunks of ``chunk``."""

    def system_logits(engine, check_ids, n_prompt: int, uid: int = 2**30):
        import numpy as np

        for c0 in range(0, n_prompt, chunk):
            out = engine.put([uid], [check_ids[c0:min(c0 + chunk, n_prompt)]], sample=None)
        got = [np.asarray(out, np.float32)[0]]
        for j in range(n_prompt, len(check_ids)):
            got.append(np.asarray(engine.put([uid], [check_ids[j:j + 1]], sample=None), np.float32)[0])
        engine.flush(uid)
        return got

    return system_logits


def decide(routed, check: dict, ck: dict, window) -> dict:
    """``serve_routed.decide`` on the lower quantile, and on the upper one
    the same way; correct only if both hold."""
    upper = routed.decide(check, float(ck["upper_quantile"]), float(ck["upper_tol"]), window)
    out = routed.decide(check, float(ck["quantile"]), float(ck["quantile_tol"]), window)
    out.update(rel_l2_upper_quantile=upper["rel_l2_quantile"], rel_l2_high=upper["rel_l2_low"],
               rel_l2_high_by_side=upper["rel_l2_low_by_side"], upper_tol=upper["quantile_tol"],
               ok=bool(out["ok"] and upper["ok"]))
    return out


def build(cell: dict, seed: int, devices, rehearsal: bool, phases):
    from benchmark.lib import loader

    serve = loader.load_module("builders", "serve", cell["root"])
    routed = loader.load_module("builders", "serve_routed", cell["root"])
    cf, tf = cell["config_file"], cell["traffic_file"]
    chunk = int(tf.get("gateway", {}).get("token_budget") or cf["engine"]["max_ragged_batch_size"])
    serve.make_params = make_params(serve, float(cf["check"]["bias_std"]))
    serve.system_logits = chunked_logits(chunk)
    system = serve.build(cell, seed, devices, rehearsal, phases)
    system.check = decide(routed, system.check, cf["check"], routed.window_of(cf))
    return system
