"""Flops profiler.

Analog of the reference ``profiling/flops_profiler/profiler.py`` (1,244 LoC)
which monkey-patches torch functional ops to count MACs per module. The
TPU-native mechanism is XLA's own cost analysis: jit-compile the step, ask the
compiled executable for ``cost_analysis()`` (flops, bytes accessed) — exact
for the compiled program, no patching. ``get_model_profile`` mirrors the
reference's public helper of the same name.
"""

import jax

from ..utils.logging import log_dist


def training_flops_per_token(n_params, num_layers=None, hidden_size=None, seq_len=None):
    """Model training FLOPs per token, PaLM convention: 6 FLOPs per parameter
    (fwd 2 + bwd 4) plus the attention score/context term when the
    architecture is known. The numerator of every MFU this repo reports
    (``monitor/metrics.py::compute_mfu``, engine step telemetry)."""
    flops = 6.0 * float(n_params)
    if num_layers and hidden_size and seq_len:
        flops += 12.0 * num_layers * hidden_size * seq_len
    return flops


def cost_analysis_dict(compiled):
    """``compiled.cost_analysis()`` as ONE flat dict (a jax that wraps the
    result in a single-element list is unwrapped)."""
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    return dict(cost or {})


def analyze_fn(fn, *example_args, **example_kwargs):
    """Compile ``fn`` and return {'flops': float, 'bytes accessed': float, ...}."""
    lowered = jax.jit(fn).lower(*example_args, **example_kwargs)
    return cost_analysis_dict(lowered.compile())


def build_module_profile(model, batch_size: int, seq_len: int) -> dict:
    """Per-module MACs/params tree for a ``TransformerLM`` (reference
    ``profiler.py:507-760`` builds the same tree via torch functional hooks;
    here the MAC counts come from the op shapes directly — the identical
    arithmetic — with params counted exactly from the param subtrees, and
    ``total_flops_xla`` as the compiled-program ground truth the analytic
    total is validated against in ``tests/``).

    Returns a nested dict: each node has ``params``, ``macs``, ``flops``
    (2*MACs + elementwise terms) and optional ``children``.
    """
    import numpy as np

    cfg = model.config
    B, S = batch_size, seq_len
    H, F = cfg.hidden_size, cfg.intermediate_size
    nq, nkv, d = cfg.num_heads, cfg.num_kv_heads or cfg.num_heads, cfg.head_dim
    L, V = cfg.num_layers, cfg.vocab_size
    T = B * S

    params = jax.eval_shape(lambda r: model.init(r, None), jax.random.PRNGKey(0))

    def count_params(subtree):
        return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(subtree))

    def node(name, macs, p, elementwise=0.0, children=None):
        n = {"name": name, "macs": float(macs), "params": int(p),
             "flops": 2.0 * macs + elementwise}
        if children:
            n["children"] = children
            n["macs"] = sum(c["macs"] for c in children)
            n["flops"] = sum(c["flops"] for c in children)
        return n

    blocks_p = params.get("blocks", {})
    per_layer_p = count_params(blocks_p) // max(L, 1)

    b = 1 if cfg.use_bias else 0
    qkv = node("qkv_proj", T * H * (nq + 2 * nkv) * d,
               H * (nq + 2 * nkv) * d + b * (nq + 2 * nkv) * d)
    scores = node("attn_scores", T * S * nq * d, 0)
    context = node("attn_context", T * S * nq * d, 0)
    out_proj = node("out_proj", T * nq * d * H, nq * d * H + b * H)
    attn = node("attention", 0, 0, children=[qkv, scores, context, out_proj])
    attn["params"] = qkv["params"] + out_proj["params"]

    gate_macs = T * H * F if cfg.mlp == "swiglu" else 0
    mlp = node("mlp", T * H * F + gate_macs + T * F * H,
               H * F * (2 if cfg.mlp == "swiglu" else 1) + F * H + b * (F + H),
               elementwise=4.0 * T * F)
    # rmsnorm: scale only; layernorm: scale + bias
    norm_p = 2 * H * (2 if cfg.norm == "layernorm" else 1)
    norms = node("layernorms", 0, norm_p, elementwise=2 * 5.0 * T * H)
    layer = node("decoder_layer", 0, 0, children=[attn, mlp, norms])
    layer["params"] = per_layer_p

    blocks = {"name": f"blocks (x{L})", "params": count_params(blocks_p),
              "macs": L * layer["macs"], "flops": L * layer["flops"],
              "children": [layer]}

    embed = node("embed", 0, count_params(params.get("embed", {}))
                 + count_params(params.get("pos_embed", {})), elementwise=float(T * H))
    final_norm = node("final_norm", 0, count_params(params.get("final_norm", {})),
                      elementwise=5.0 * T * H)
    unembed = node("lm_head", T * H * V,
                   0 if cfg.tie_embeddings else count_params(params.get("lm_head", {})))

    children = [embed, blocks, final_norm, unembed]
    root = {"name": type(model).__name__, "params": count_params(params),
            "macs": sum(c["macs"] for c in children),
            "flops": sum(c["flops"] for c in children),
            "children": children,
            "batch_size": B, "seq_len": S}
    return root


def render_module_profile(root: dict, depth: int = -1) -> str:
    """Reference ``print_model_profile`` rendering: one line per module with
    params, MACs, fwd FLOPs and the share of the model total."""
    total = max(root["flops"], 1.0)
    lines = [f"{'module':<28} {'params':>10} {'MACs':>12} {'fwd FLOPs':>12} {'% fwd':>7}"]

    def walk(n, indent, d):
        lines.append(f"{'  ' * indent + n['name']:<28} {_num_to_string(n['params']):>10} "
                     f"{_num_to_string(n['macs']):>12} {_num_to_string(n['flops']):>12} "
                     f"{100.0 * n['flops'] / total:>6.1f}%")
        if d != 0:
            for c in n.get("children", ()):
                walk(c, indent + 1, d - 1)

    walk(root, 0, depth)
    return "\n".join(lines)


class FlopsProfiler:
    """Engine-integrated profiler (reference ``FlopsProfiler:28``).

    ``start_profile`` arms the profiler (and stamps a wall-clock origin);
    ``profile_step`` records the compiled step's XLA cost analysis;
    ``stop_profile`` freezes the captured numbers; ``print_model_profile``
    renders the per-module tree when a model was attached."""

    def __init__(self, engine=None, model=None):
        self.engine = engine
        self.model = model or (engine is not None and getattr(engine, "module", None)) or None
        self.profile = {}
        self.module_profile = None
        self._active = False
        self._t0 = None

    def start_profile(self, ignore_list=None):
        import time

        self._active = True
        self._t0 = time.time()
        self.profile = {}
        self.module_profile = None

    def stop_profile(self):
        import time

        if self._active and self._t0 is not None:
            self.profile.setdefault("wall_seconds", time.time() - self._t0)
        self._active = False

    def end_profile(self):
        self.profile = {}
        self.module_profile = None
        self._active = False

    def get_total_flops(self, as_string=False):
        f = self.profile.get("flops", 0.0)
        return _num_to_string(f) + "FLOPS" if as_string else f

    def get_total_params(self, as_string=False):
        p = self.profile.get("params", 0.0)
        if not p and self.module_profile:
            p = self.module_profile["params"]
        return _num_to_string(p) if as_string else p

    def get_total_duration(self, as_string=False):
        dt = self.profile.get("wall_seconds", 0.0)
        return f"{dt:.2f} s" if as_string else dt

    def profile_step(self, step_fn, *args):
        self.profile.update(analyze_fn(step_fn, *args))
        return self.profile

    def profile_model(self, batch_size: int, seq_len: int):
        """Build the per-module breakdown (requires an attached model)."""
        if self.model is None:
            raise ValueError("FlopsProfiler needs a model (or engine) for the per-module profile")
        self.module_profile = build_module_profile(self.model, batch_size, seq_len)
        self.profile.setdefault("params", self.module_profile["params"])
        return self.module_profile

    def print_model_profile(self, profile_step=1, module_depth=-1, top_modules=1,
                            detailed=True, output_file=None):
        parts = []
        if self.profile:
            parts.append(f"program totals (XLA cost analysis): {self.profile}")
        if self.module_profile is not None:
            parts.append(render_module_profile(self.module_profile,
                                               depth=module_depth if detailed else 1))
        text = "\n".join(parts) or "flops profile: (nothing captured — call "\
            "profile_step and/or profile_model first)"
        if output_file:
            with open(output_file, "w") as f:
                f.write(text + "\n")
        log_dist(text, ranks=[0])
        return text


def get_model_profile(model, args=(), kwargs=None, print_profile=True, detailed=True, as_string=True, **_):
    """Reference public helper: profile one forward of ``model``.

    ``model`` follows the framework protocol (init/apply)."""
    import jax.numpy as jnp

    rng = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda r: model.init(r, None), rng)
    n_params = sum(int(jnp.prod(jnp.asarray(x.shape))) for x in jax.tree_util.tree_leaves(params))
    real_params = jax.jit(lambda r: model.init(r, None))(rng)
    cost = analyze_fn(model.apply, real_params, *args, **(kwargs or {}))
    flops = cost.get("flops", 0.0)
    if print_profile:
        log_dist(f"params={_num_to_string(n_params)} fwd flops={_num_to_string(flops)}", ranks=[0])
    if as_string:
        return _num_to_string(flops), _num_to_string(n_params)
    return flops, n_params


def _num_to_string(num, precision=2):
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(num) >= div:
            return f"{num / div:.{precision}f} {unit}"
    return str(num)
