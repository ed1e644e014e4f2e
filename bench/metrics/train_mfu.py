"""Model FLOP utilization of the training window: (6 x matmul
parameters + causal attention) operations per token, times tokens per
second, over the chip's bf16 peak, in percent; recomputation does not
count."""
from bench.lib.counts import dense_lm_train_flops_per_token


def read(run):
    if run.peaks is None:
        return None
    per_token = dense_lm_train_flops_per_token(run.config,
                                               run.traffic["seq"])
    return 100.0 * per_token * run.values["tokens_per_s"] \
        / run.peaks["bf16_flops"]
