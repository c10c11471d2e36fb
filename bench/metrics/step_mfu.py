"""The whole step's share of the chips' bf16 peak, in %: model FLOPs per
trained token (``bench/work.py``: 6 per matmul parameter plus causal
attention, recomputation not counted) times the window's tokens per second,
over chips times the peak of the device kind (``bench/peaks.json``)."""
from bench import work


def read(run):
    if not run.peak:
        return None
    flops = work.model_flops_per_token(run.arch, run.job["seq"])
    return 100.0 * flops * run.tokens_per_s / (run.chips * run.peak["bf16_flops"])
