"""Operations of MCLR (logits = x W + b) from its shapes."""


def forward_per_sample(cfg) -> int:
    """Multiply-adds of x W (two operations each) plus the bias add."""
    d, c = cfg["n_features"], cfg["n_classes"]
    return 2 * d * c + c
