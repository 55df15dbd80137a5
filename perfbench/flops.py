"""Operations and compulsory bytes of one federated round, from shapes.

The count is what the algorithm needs, whatever program computes it: the
forward and backward pass of every client's full-batch step, and the forward
pass behind the round's metrics (on the new global model: other parameters,
so not a recomputation). A multiply-add is two operations. Bytes are the
compulsory traffic: each pass reads its rows once, the step reads and writes
parameters and both Adam moments, the average reads every client's
parameters and writes the global back to every slot. Activations are not
counted: a roofline share computed from these bytes says how far the program
is from the least traffic, not from XLA's own.
"""

from __future__ import annotations

F32 = 4


def mlp_layers(input_dim: int, hidden_sizes, num_classes: int):
    dims = (input_dim, *hidden_sizes, num_classes)
    return list(zip(dims[:-1], dims[1:]))


def mlp_forward_flops(rows: int, input_dim, hidden_sizes, num_classes):
    return sum(2 * rows * i * o
               for i, o in mlp_layers(input_dim, hidden_sizes, num_classes))


def mlp_params(input_dim, hidden_sizes, num_classes):
    return sum(i * o + o
               for i, o in mlp_layers(input_dim, hidden_sizes, num_classes))


def convnet_shapes(image_shape, conv_channels, hidden: int, num_classes: int):
    """Per-image multiply-adds of each layer and the parameter count."""
    h, w, cin = image_shape
    macs, params = [], 0
    for cout in conv_channels:
        macs.append(h * w * 9 * cin * cout)
        params += 9 * cin * cout + cout
        cin, h, w = cout, h // 2, w // 2
    flat = h * w * cin
    macs += [flat * hidden, hidden * num_classes]
    params += flat * hidden + hidden + hidden * num_classes + num_classes
    return macs, params


def round_cost(model: dict, num_clients: int, rows_per_client: int,
               features: int) -> dict:
    """``{'flops', 'bytes', 'params'}`` of one round over all clients."""
    rows = num_clients * rows_per_client
    if model["kind"] == "mlp":
        args = (model["input_dim"], model["hidden_sizes"], model["num_classes"])
        fwd = mlp_forward_flops(rows, *args)
        first = 2 * rows * args[0] * (args[1][0] if args[1] else args[2])
        params = mlp_params(*args)
    elif model["kind"] == "convnet":
        macs, params = convnet_shapes(model["image_shape"],
                                      model["conv_channels"],
                                      model["hidden_sizes"][0],
                                      model["num_classes"])
        fwd = 2 * rows * sum(macs)
        first = 2 * rows * macs[0]
    else:
        raise KeyError(model["kind"])
    # forward + backward (2x forward, less the input gradient of the first
    # layer, which nothing needs) + the metrics' forward
    flops = fwd + (2 * fwd - first) + fwd
    row_bytes = (features + 2) * F32            # x, label, mask
    state = num_clients * params * F32
    bytes_ = (2 * rows * row_bytes              # training and metrics passes
              + 6 * state                       # params, m, v: read and written
              + 2 * state)                      # average read, global written
    return {"flops": float(flops), "bytes": float(bytes_), "params": params}


def roofline(cost: dict, peaks: dict, chips: int, device_round_s: float):
    """Least time at the peaks over the measured device time, and which
    peak bounds: ``(percent, 'flops' | 'bytes')``."""
    t_flops = cost["flops"] / (chips * peaks["bf16_flops_per_s"])
    t_bytes = cost["bytes"] / (chips * peaks["hbm_bytes_per_s"])
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / device_round_s, bound
