"""The parts of a model's step, as ``jax.named_scope`` names.

The model code wraps each part in ``jax.named_scope(<constant>)``; a scope is
metadata (every operation traced under it carries the name on its ``op_name``
path, the compiled fusions too) and adds no operation. A device trace is read
back by these names (``benchmark/lib/op_scopes.py``): the INNERMOST word of
the vocabulary on an operation's path is its part, so ``mixer/attn_proj/dot``
is a projection and ``moe/mlp/dot`` a shared expert.
"""

EMBED = "embed"                # token (and position) embedding, a step's descriptors
ATTN_PROJ = "attn_proj"        # the norm before a mixer and its input (and gate) projections
MIXER = "mixer"                # between those and the output projection: norms a head, rope, the cache scatter, the kernel and the glue around it
SPARSE_INDEX = "sparse_index"  # a learned block selection's indexer, inside ``mixer``
ATTN_OUT = "attn_out"          # the output projection, the norm after it and the residual add of the mixer's branch
MLP = "mlp"                    # the norm, a dense MLP (shared experts too, inside ``moe``) and its residual add
MOE = "moe"                    # router, top-k, sort and pad, the grouped matmul, combine, the routing counts
LM_HEAD = "lm_head"            # final norm, unembedding, logit scale
SAMPLE = "sample"              # the token choice inside a step program
LOSS = "loss"                  # training: cross entropy on the logits
OPTIMIZER = "optimizer"        # training: gradient accumulation, clipping and the update

VOCABULARY = (EMBED, ATTN_PROJ, MIXER, SPARSE_INDEX, ATTN_OUT, MLP, MOE, LM_HEAD, SAMPLE, LOSS, OPTIMIZER)
