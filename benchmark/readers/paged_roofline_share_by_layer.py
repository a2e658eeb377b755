"""``paged_roofline_share``'s sum with each layer's own window: the least time
for the attention of every engine step in the traced window, layer by layer
(a layer whose entry in the configuration file's ``layer_types`` is
``full_attention`` sees every earlier key, any other the file's
``sliding_window``), over the traced time of the named kernels, in percent. A
configuration without ``layer_types`` gives every layer the one window, and
the number is ``paged_roofline_share``'s. ``args``: ``{"kernels": [...]}``."""

from benchmark.lib import opcount
from benchmark.lib.xplane import kernel_seconds


def layer_windows(config_file: dict) -> list:
    """The window of each of the configuration's layers; None: all keys."""
    n, window = config_file["num_hidden_layers"], config_file.get("sliding_window")
    kinds = config_file.get("layer_types") or ["sliding_attention"] * n
    return [None if kind == "full_attention" else window for kind in kinds[:n]]


def read(ctx):
    reduced, peaks = ctx.get("reduced"), ctx["peaks"]
    if not reduced or peaks is None or ctx["kind"] != "serve" or not ctx.get("trace_window"):
        return None
    system, cfg = ctx["system"], ctx["system"].cfg
    windows = layer_windows(ctx["cell"]["config_file"])
    by_window = {w: windows.count(w) for w in set(windows)}
    t0, t1 = ctx["trace_window"]
    seen, least = {}, 0.0
    for step in system.steps:
        calls = []  # one list of rows per attention call of a layer
        if step["kind"] == "put":
            calls.append([(seen.get(u, 0), n) for u, n in zip(step["uids"], step["sizes"])])
        else:
            calls.extend([(seen.get(u, 0) + j, 1) for u in step["uids"]] for j in range(step["sizes"][0]))
        for uid, size in zip(step["uids"], step["sizes"]):
            seen[uid] = seen.get(uid, 0) + size
        if step["t0"] < t0 or step["t1"] > t1:
            continue
        for rows in calls:
            for window, layers in by_window.items():
                flops, nbytes = opcount.paged_attention_cost(rows, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                                                             window, system.kv_itemsize, system.kv_itemsize)
                least += layers * opcount.min_seconds(flops, nbytes, peaks)[0]
    measured = kernel_seconds(reduced, ctx["args"]["kernels"])
    return 100.0 * least / measured if measured > 0 else None
