"""Model FLOP utilisation of the window, in %: tokens per second times
model FLOPs per token (forward and backward, no recomputation) over the
chips' bf16 peak."""

from chipbench import work


def read(record: dict):
    if not record.get("tokens_per_s"):
        return None
    peak = work.peaks(record["device_kind"])["bf16_flops_per_s"]
    return (100.0 * record["tokens_per_s"] * record["flops_per_token"]
            / (record["chips"] * peak))
