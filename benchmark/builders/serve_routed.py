"""The serving system under test for a model with routed experts: what
``builders/serve.py`` builds (one ``InferenceEngineV2`` replica behind
``ServingGateway``, weights from the seed, the same warm-up and step log), and
a ``correct`` that a flipped expert cannot fool.

Why another decision. The top-k set of a token is a discontinuous function of
the router's logits: bf16 activations against the float32 reference put some
expert ranked k and k+1 in the other order at some (position, layer), and from
there on that position's logits differ by the two experts' outputs, many times
the rounding error. ``serve.py`` holds the LARGEST relative L2 error over its
positions to one tolerance; with flips in it, a tolerance that passes every
sound seed catches nothing. A flip can only add error to its position, so the
positions whose routing agreed are the low end of the distribution:

* the ``quantile`` (lower quartile) of the per-position relative L2 error is
  held to ``quantile_tol``, the tight limit: it is the rounding error of the
  positions that no flip touched, and a lower precision moves all of it;
* every position is held to ``rel_l2_tol``, the loose limit, which a wrong
  position, rope table or block table still breaks (such an error is of
  order one at every position behind it; a wrong WINDOW is not: see below);
* everything is finite.

The positions are the last of a ``prompt_tokens`` prefill and ``decode_tokens``
further ones decoded one at a time through the cache, and they run PAST the
attention window of the window layers (``sliding_window``): a position below
it reads every earlier key in every layer, so there the window mask is the
causal mask and only the rope tells a window layer from a full one. The
positions are therefore split where the window begins to leave keys out
(position ``sliding_window``: its query no longer sees key 0), and the lower
quartile of EACH side is held to ``quantile_tol``: a window that the paged
kernels ignore or bound elsewhere adds error to every position past it and to
none before it, which one quartile over all positions would not see.
``serve.py`` is loaded by its path and does the building and the comparing of
each position; this file adds the decision.
"""


def decide(check: dict, quantile: float, quantile_tol: float, window=None) -> dict:
    """``check`` as ``serve.py`` left it (``positions``, ``rel_l2`` per
    position, ``finite``, ``ok`` = finite and every position within
    ``rel_l2_tol``) with the low quantile of the errors on each side of
    ``window`` (one side where it is None or no position lies on the other)
    and the decision on them added. ``rel_l2_low`` is the larger of the two.
    The per-position lists, a thousand entries long, are kept rounded and the
    argmax flags as their share."""
    import numpy as np

    errors = np.asarray(check["rel_l2"], np.float64)
    past = np.zeros(errors.shape, bool) if window is None else np.asarray(check["positions"]) >= window
    sides = {name: float(np.quantile(errors[mask], quantile))
             for name, mask in (("within_window", ~past), ("past_window", past)) if mask.any()}
    out = {k: v for k, v in check.items() if k not in ("positions", "argmax_equal")}
    out.update(rel_l2=[round(float(e), 6) for e in errors], rel_l2_quantile=quantile, rel_l2_low=max(sides.values()),
               rel_l2_low_by_side=sides, positions_past_window=int(past.sum()), window=window,
               rel_l2_median=float(np.median(errors)), rel_l2_max=float(errors.max()), quantile_tol=quantile_tol,
               within_loose=bool(check["ok"]))
    if "positions" in check:
        out["positions_first_last"] = [int(check["positions"][0]), int(check["positions"][-1])]
    if "argmax_equal" in check:
        out["argmax_equal_share"] = float(np.mean(check["argmax_equal"]))
    out["ok"] = bool(check["ok"] and out["rel_l2_low"] <= quantile_tol)
    return out


def window_of(config_file: dict):
    """The attention window of the configuration's window layers, None where
    it has none."""
    return config_file.get("sliding_window") if "sliding_attention" in config_file.get("layer_types", ()) else None


def build(cell: dict, seed: int, devices, rehearsal: bool, phases):
    from benchmark.lib import loader

    serve = loader.load_module("builders", "serve", cell["root"])
    system = serve.build(cell, seed, devices, rehearsal, phases)
    cf = cell["config_file"]
    system.check = decide(system.check, float(cf["check"]["quantile"]), float(cf["check"]["quantile_tol"]),
                          window_of(cf))
    return system
