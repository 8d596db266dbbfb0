"""The whole step's share of the chips' bf16 peak: model operations per
token (forward and backward, recomputation not counted) times the traced
run's tokens per second."""


def read(run):
    if run.peak is None:
        return None
    cfg = run.cell.config
    flops = run.model().flops_per_token(cfg, int(run.cell.traffic["seq_len"]))
    return 100.0 * flops * run.got["tokens_per_s"] / (run.chips * run.peak["bf16_flops"])
