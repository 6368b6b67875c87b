"""The flash attention kernels' share of their roofline, in %: the least
time of the step's kernel calls over the self time of the device
operations named ``flash_attention`` per traced step.

Per layer the step calls the forward kernel once, and once more where
the traffic rematerialises each layer, then the dK/dV and the dQ kernel
(``work.FLASH_CALLS``).  A call's least time is the larger of its FLOPs
over the bf16 peak and its bytes over the HBM bandwidth, at the cell's
batch, sequence, heads and head size, counting the causal work alone.
The step's work is split evenly over the chips; where a chip holds more
than its share, the share reads low."""

import jax.numpy as jnp

from chipbench import work


def read(record: dict):
    ms = (record.get("trace") or {}).get("op_ms_per_step", {}).get(
        "flash_attention")
    if not ms:
        return None
    c, t = record["config"], record["traffic"]
    peak = work.peaks(record["device_kind"])
    calls = {"forward": 2 if t["remat"] else 1, "dkv": 1, "dq": 1}
    least_s = 0.0
    for call, n in calls.items():
        flops, nbytes = work.flash_attention(
            t["global_batch"], t["seq_len"], c["num_attention_heads"],
            c["head_dim"], jnp.dtype(c["compute_dtype"]).itemsize,
            call=call)
        least_s += n * max(flops / peak["bf16_flops_per_s"],
                           nbytes / peak["hbm_bytes_per_s"])
    least_s *= c["num_hidden_layers"] / record["chips"]
    return 100.0 * 1e3 * least_s / ms
