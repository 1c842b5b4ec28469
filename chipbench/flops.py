"""Operations a model needs, from its shapes alone."""


def lm_matmul_params(cfg):
    """Matmul parameters a decoder holds: the layers' projections and
    feed-forward, and the output head (tied, but multiplied all the same).
    Embedding look-ups, positions and norm gains do no matmul."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * f) + cfg["vocab_size"] * d


def lm_train_flops_per_token(cfg, seq):
    """Forward + backward, no recompute: 6 per matmul parameter, plus causal
    attention's scores and weighted sum, 6 * n_layer * seq * d_model
    (2 matmuls x 2 FLOPs x seq x d, halved by causality, x 3 for
    forward + backward)."""
    return 6 * lm_matmul_params(cfg) + 6 * cfg["n_layer"] * seq * cfg["n_embd"]
