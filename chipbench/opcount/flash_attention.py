"""What causal flash attention has to compute in a training step: forward two
matmuls (Q K^T and P V) over the causal half of the S x S square, backward
four (dV, dP, dQ, dK). The recomputation of the scores in the backward pass is
the kernel's choice, not the algorithm's need, and is NOT counted. Compute
bound at these shapes (S = 1024, head 64: ~500 FLOPs per byte of Q, K, V)."""


def step_flops(batch, seq, sz):
    """Forward + backward FLOPs of all layers' attention in one step."""
    dh = sz["n_embd"] // sz["n_head"]
    one_matmul = 2 * batch * sz["n_head"] * seq * seq * dh / 2    # causal
    return sz["n_layer"] * (2 + 4) * one_matmul


def step_bytes(batch, seq, sz, bytes_per_value=2):
    """Q, K, V, O read or written once forward, and with their gradients
    once backward."""
    qkvo = 4 * batch * seq * sz["n_embd"] * bytes_per_value
    return sz["n_layer"] * 3 * qkvo


def work_in_slice(obs, pattern=None):
    """Steps per second of the window times the traced slice's length."""
    if obs.get("kind") != "train" or not obs.get("trace"):
        return None
    steps = obs["steps_in_window"] / obs["window_s"] * obs["trace"]["window_s"]
    return {"flops": steps * step_flops(obs["batch"], obs["seq"], obs["sizes"]),
            "bytes": steps * step_bytes(obs["batch"], obs["seq"], obs["sizes"])}
