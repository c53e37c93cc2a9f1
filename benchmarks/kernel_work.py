"""The Pallas kernels in a reduced trace: the time of those whose name holds
a given part, and the work the flash kernels are required to do, from shapes
alone — the two halves of a kernel's share of its roofline.

The program names every ``pallas_call`` (``flash_fwd_packed``,
``flash_bwd_packed_dq``, ``xentropy_stats`` …) and the compiled step wraps
the name in its transforms (``jvp_flash_fwd_packed_.3``,
``transpose_jvp_flash_bwd_packed_dq__.17``), so the readers match a part of
the name. A program that names nothing (``jvp__.N``) matches nothing, and
its readers return ``None``.
"""
from benchmarks import flops

FLASH_FORWARD, FLASH_BACKWARD, CROSS_ENTROPY = "flash_fwd", "flash_bwd", "xentropy"


def kernel_ms(run, part):
    """Milliseconds a traced step, mean over chips, spent in the device
    operations whose name holds ``part``; ``None`` without a device trace or
    where no operation matches."""
    trace = run.get("trace")
    if not trace or not trace.get("chips") or not run.get("step_s"):
        return None
    found = [s for name, s in trace["ops_s"].items() if part in name]
    if not found:
        return None
    return 1e3 * sum(found) / len(run["step_s"])


def flash_work(run, backward=False):
    """(operations, bytes) that attention requires of one chip in one step.

    Operations: the attention term ``flops.forward_flops_per_token`` counts
    (causal half, all query heads; QK^T and PV), twice that for the backward
    pass (dV, dP, dQ, dK; recomputing S is the kernel's choice, not required
    work). Bytes, bf16, each tensor once: forward reads q, k, v and writes o
    and one float32 log-sum-exp a row and head; backward reads q, k, v, o, do
    and the log-sum-exp and writes dq, dk, dv. q-like tensors at ``n_head``
    width, k-like at ``n_kv_head`` width."""
    d, seq = run["dims"], run["seq"]
    tokens = run["tokens"] / run["steps"] / run["chips"]
    operations = (flops.forward_flops_per_token(d, seq) - 2 * flops.matmul_params(d)) * tokens
    q_like, k_like = d["n_head"] * d["head_dim"], d["n_kv_head"] * d["head_dim"]
    each = 4 if backward else 2        # q, o, do, dq | k, v, dk, dv; or q, o | k, v
    per_token = 2 * each * (q_like + k_like) + 4 * d["n_head"]
    return (2 if backward else 1) * operations, d["n_layer"] * tokens * per_token


def flash_roofline_pct(run, backward=False):
    """The least time the chip could take for the required attention work
    (the larger of operations over the bf16 peak and bytes over the HBM
    peak) over the time the flash kernels took; ``None`` where no kernel
    time can be read."""
    took_ms = kernel_ms(run, FLASH_BACKWARD if backward else FLASH_FORWARD)
    if took_ms is None:
        return None
    operations, nbytes = flash_work(run, backward)
    peaks = run["peaks"]
    least_s = max(operations / peaks["flops_per_s"]["bfloat16"],
                  nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least_s / took_ms
