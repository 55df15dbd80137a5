"""The mega-kernel ATTEMPT: one Pallas kernel per federated round — a
preserved NEGATIVE result (PERF.md 'Earlier records', round 4).

The whole round — per-client train fwd+bwd+Adam, eval confusion matrix,
and the weighted-average accumulation — runs in a single pallas_call
with activations never leaving VMEM. It is numerically right (asserts
below: one-round parity vs the production XLA round at matmul-precision
level, and trajectory agreement at round 100), and it is ~3x SLOWER
than the XLA round on the v5e (~62 us vs ~22 us marginal): Mosaic's
matmul codegen for these pad-dominated shapes (K=14, N=2 against the
128-lane MXU) loses far more than fusing the activation streams saves.
Stage bisect: the forward alone costs 18.7 us in-kernel vs the entire
XLA round's 21.5 us.

Kept runnable so the conclusion stays reproducible; do not wire into
the production path. Run: ``python benchmarks/mega_kernel_attempt.py``
(~2 min on the v5e; requires the TPU backend for the timing part).
"""
import sys
sys.path.insert(0, __file__.rsplit("/", 2)[0])

import time, functools, numpy as np, jax, jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from fedtpu.config import DataConfig, ModelConfig, OptimConfig, ShardConfig, default_income_csv
from fedtpu.data.tabular import load_tabular_dataset
from fedtpu.data.sharding import pack_clients
from fedtpu.models import build_model
from fedtpu.ops import build_optimizer
from fedtpu.parallel import make_mesh
from fedtpu.parallel.round import build_round_fn, init_federated_state
from fedtpu.utils.trees import clone
from fedtpu.utils.timing import force_fetch, marginal_slope

ds = load_tabular_dataset(DataConfig(csv_path=default_income_csv()))
packed = pack_clients(ds.x_train, ds.y_train, ShardConfig(num_clients=8))
xd = jnp.asarray(packed.x); yd = jnp.asarray(packed.y).astype(jnp.int32); md = jnp.asarray(packed.mask)
C, N, D = xd.shape
K = 2
dims = [D, 50, 200, K]
NL = 3
B1, B2, EPS = 0.9, 0.999, 1e-8
LR0, GAMMA, STEPSZ = 0.004, 0.5, 30

ohm = (jax.nn.one_hot(yd, K, dtype=jnp.float32) * md[..., None])   # (C,N,K) masked one-hot
mask3 = md[..., None]                                               # (C,N,1)

def kernel(scalars_ref, wn_ref, den_ref, x_ref, ohm_ref, m_ref, *refs):
    c = pl.program_id(0)
    lr = scalars_ref[0]; c1 = scalars_ref[1]; c2 = scalars_ref[2]
    wn = wn_ref[c]; denom = den_ref[c]
    iw = lambda i: refs[3*i][0]
    imw = lambda i: refs[3*i+1][0]
    inw = lambda i: refs[3*i+2][0]
    ib = lambda i: refs[3*NL + 3*i][pl.ds(c, 1), :]
    imb = lambda i: refs[3*NL + 3*i+1][pl.ds(c, 1), :]
    inb = lambda i: refs[3*NL + 3*i+2][pl.ds(c, 1), :]
    o = 6*NL
    out_aggW = lambda i: refs[o + i]
    out_aggB = lambda i: refs[o + NL + i]
    out_muw = lambda i: refs[o + 2*NL + i]
    out_nuw = lambda i: refs[o + 3*NL + i]
    out_mub = lambda i: refs[o + 4*NL + i]
    out_nub = lambda i: refs[o + 5*NL + i]
    out_loss = refs[o + 6*NL]
    out_conf = refs[o + 6*NL + 1]

    x = x_ref[0]          # (N, D)
    oh = ohm_ref[0]       # (N, K) masked one-hot
    msk = m_ref[0]        # (N, 1)
    hs = [x]
    h = x
    for i in range(NL):
        z = jnp.dot(h, iw(i), preferred_element_type=jnp.float32) + ib(i)
        h = jnp.maximum(z, 0.0) if i < NL - 1 else z
        hs.append(h)
    logits = hs[-1]
    zmax = jnp.max(logits, axis=-1, keepdims=True)
    ls = logits - zmax
    lse = jnp.log(jnp.sum(jnp.exp(ls), axis=-1, keepdims=True))
    logp = ls - lse
    loss = -jnp.sum(logp * oh) / denom
    out_loss[pl.ds(c, 1), :] = jnp.full((1, 128), loss, jnp.float32)
    p = jnp.exp(logp)
    dz = (p * msk - oh) / denom
    gW, gB = [None]*NL, [None]*NL
    for i in range(NL - 1, -1, -1):
        a = hs[i]
        gW[i] = jax.lax.dot_general(a, dz, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        gB[i] = jnp.sum(dz, axis=0, keepdims=True)
        if i > 0:
            dh = jax.lax.dot_general(dz, iw(i), (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            dz = dh * (hs[i] > 0.0).astype(jnp.float32)
    trainedW, trainedB = [None]*NL, [None]*NL
    for i in range(NL):
        for (g, pv, mu, nu, st_mu, st_nu, is_w) in (
                (gW[i], iw(i), imw(i), inw(i), out_muw(i), out_nuw(i), True),
                (gB[i], ib(i), imb(i), inb(i), out_mub(i), out_nub(i), False)):
            mu2 = B1 * mu + (1 - B1) * g
            nu2 = B2 * nu + (1 - B2) * g * g
            newp = pv - lr * (mu2 / c1) / (jnp.sqrt(nu2 / c2) + EPS)
            if is_w:
                st_mu[0] = mu2
                st_nu[0] = nu2
                trainedW[i] = newp
                @pl.when(c == 0)
                def _():
                    out_aggW(i)[...] = jnp.zeros_like(out_aggW(i))
                out_aggW(i)[...] += wn * newp
            else:
                st_mu[pl.ds(c, 1), :] = mu2
                st_nu[pl.ds(c, 1), :] = nu2
                trainedB[i] = newp
                @pl.when(c == 0)
                def _():
                    out_aggB(i)[...] = jnp.zeros_like(out_aggB(i))
                out_aggB(i)[pl.ds(0, 1), :] += wn * newp
    h = x
    for i in range(NL):
        z = jnp.dot(h, trainedW[i], preferred_element_type=jnp.float32) + trainedB[i]
        h = jnp.maximum(z, 0.0) if i < NL - 1 else z
    best = h[:, 0:1]
    idx = jnp.zeros((N, 1), jnp.float32)
    for k in range(1, K):
        cur = h[:, k:k+1]
        better = cur > best
        idx = jnp.where(better, jnp.float32(k), idx)
        best = jnp.maximum(best, cur)
    pred_oh = jnp.concatenate([(idx == jnp.float32(k)).astype(jnp.float32)
                               for k in range(K)], axis=1)      # (N, K)
    conf = jax.lax.dot_general(oh, pred_oh, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)
    out_conf[0] = jnp.pad(conf, ((0, 8-K), (0, 128-K)))

def fused_round(flat, scalars, wn_arr, den_arr):
    Ws, Bs, muW, nuW, muB, nuB = flat
    args = [scalars, wn_arr, den_arr, xd, ohm, mask3]
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, N, D), lambda c: (c, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, N, K), lambda c: (c, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, N, 1), lambda c: (c, 0, 0), memory_space=pltpu.VMEM)]
    for i in range(NL):
        for t in (Ws[i], muW[i], nuW[i]):
            args.append(t)
            in_specs.append(pl.BlockSpec((1, dims[i], dims[i+1]), lambda c: (c, 0, 0), memory_space=pltpu.VMEM))
    for i in range(NL):
        for t in (Bs[i], muB[i], nuB[i]):
            args.append(t)
            in_specs.append(pl.BlockSpec((C, dims[i+1]), lambda c: (0, 0), memory_space=pltpu.VMEM))
    out_shapes, out_specs = [], []
    for i in range(NL):
        out_shapes.append(jax.ShapeDtypeStruct((dims[i], dims[i+1]), jnp.float32))
        out_specs.append(pl.BlockSpec((dims[i], dims[i+1]), lambda c: (0, 0), memory_space=pltpu.VMEM))
    for i in range(NL):
        out_shapes.append(jax.ShapeDtypeStruct((8, dims[i+1]), jnp.float32))
        out_specs.append(pl.BlockSpec((8, dims[i+1]), lambda c: (0, 0), memory_space=pltpu.VMEM))
    for _ in range(2):
        for i in range(NL):
            out_shapes.append(jax.ShapeDtypeStruct((C, dims[i], dims[i+1]), jnp.float32))
            out_specs.append(pl.BlockSpec((1, dims[i], dims[i+1]), lambda c: (c, 0, 0), memory_space=pltpu.VMEM))
    for _ in range(2):
        for i in range(NL):
            out_shapes.append(jax.ShapeDtypeStruct((C, dims[i+1]), jnp.float32))
            out_specs.append(pl.BlockSpec((C, dims[i+1]), lambda c: (0, 0), memory_space=pltpu.VMEM))
    out_shapes.append(jax.ShapeDtypeStruct((C, 128), jnp.float32))
    out_specs.append(pl.BlockSpec((C, 128), lambda c: (0, 0), memory_space=pltpu.VMEM))
    out_shapes.append(jax.ShapeDtypeStruct((C, 8, 128), jnp.float32))
    out_specs.append(pl.BlockSpec((1, 8, 128), lambda c: (c, 0, 0), memory_space=pltpu.VMEM))
    outs = pl.pallas_call(kernel, grid=(C,), in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shapes)(*args)
    aggW = outs[:NL]
    aggB = [outs[NL+i][0] for i in range(NL)]
    muW2 = outs[2*NL:3*NL]; nuW2 = outs[3*NL:4*NL]
    muB2 = outs[4*NL:5*NL]; nuB2 = outs[5*NL:6*NL]
    loss = outs[6*NL][:, 0]
    conf = outs[6*NL+1][:, :K, :K]
    return aggW, aggB, muW2, nuW2, muB2, nuB2, loss, conf

mesh = make_mesh(num_clients=8)
init_fn, apply_fn = build_model(ModelConfig(input_dim=D, num_classes=K))
tx = build_optimizer(OptimConfig())
state0 = init_federated_state(jax.random.key(0), mesh, 8, init_fn, tx)
xla_step = build_round_fn(mesh, apply_fn, tx, K, rounds_per_step=1)
batch = {"x": jax.device_put(packed.x), "y": jax.device_put(packed.y), "mask": jax.device_put(packed.mask)}
s_x, m_x = xla_step(clone(state0), batch)

def unpack(state):
    layers = state["params"]["layers"]
    Ws = [l["w"] for l in layers]; Bs = [l["b"] for l in layers]
    adam = state["opt_state"][0]
    muW = [l["w"] for l in adam.mu["layers"]]; nuW = [l["w"] for l in adam.nu["layers"]]
    muB = [l["b"] for l in adam.mu["layers"]]; nuB = [l["b"] for l in adam.nu["layers"]]
    return [Ws, Bs, muW, nuW, muB, nuB]

flat = unpack(clone(state0))
t = 0
lr = LR0 * (GAMMA ** (t // STEPSZ))
c1 = 1 - B1 ** (t + 1); c2 = 1 - B2 ** (t + 1)
scalars = jnp.asarray([lr, c1, c2], jnp.float32)
w = md.sum(axis=1)
wn_arr = (w / w.sum()).astype(jnp.float32)
den_arr = jnp.maximum(w, 1.0).astype(jnp.float32)
aggW, aggB, muW2, nuW2, muB2, nuB2, loss, conf = jax.jit(fused_round)(flat, scalars, wn_arr, den_arr)

for i in range(NL):
    gw_x = np.asarray(s_x["params"]["layers"][i]["w"])[0]
    gb_x = np.asarray(s_x["params"]["layers"][i]["b"])[0]
    dw = np.abs(np.asarray(aggW[i]) - gw_x).max()
    db = np.abs(np.asarray(aggB[i]) - gb_x).max()
    print(f"layer {i}: dW {dw:.2e}  dB {db:.2e}")
    # matmul-precision level (Adam's sign-sensitive rescaling at t=0
    # amplifies bf16-pass matmul differences; 2*lr = 8e-3 is the cap)
    assert dw < 8e-3 and db < 8e-3, "mega-kernel diverged from XLA round"
ld = np.abs(np.asarray(loss) - np.asarray(m_x["loss"]).ravel()).max()
print("loss diff:", ld)
assert ld < 1e-5
pc = np.asarray(m_x["per_client"]["accuracy"])
acc_pal = np.asarray(conf[:, 0, 0] + conf[:, 1, 1]) / np.asarray(conf.sum((1, 2)))
print("acc diff:", np.abs(acc_pal - pc).max())

# ---- scan R rounds with the fused kernel; trajectory + marginal timing
def make_scan(R):
    @jax.jit
    def f(flat):
        def body(carry, r):
            Ws, Bs, muW, nuW, muB, nuB = carry
            t = r
            lr_t = LR0 * jnp.power(GAMMA, (t // STEPSZ).astype(jnp.float32))
            c1_t = 1 - jnp.power(B1, (t + 1).astype(jnp.float32))
            c2_t = 1 - jnp.power(B2, (t + 1).astype(jnp.float32))
            sc = jnp.stack([lr_t, c1_t, c2_t]).astype(jnp.float32)
            aggW, aggB, muW2, nuW2, muB2, nuB2, loss, conf = fused_round(
                [Ws, Bs, muW, nuW, muB, nuB], sc, wn_arr, den_arr)
            WsN = [jnp.broadcast_to(aggW[i][None], Ws[i].shape) for i in range(NL)]
            BsN = [jnp.broadcast_to(aggB[i][None], Bs[i].shape) for i in range(NL)]
            return [list(WsN), list(BsN), list(muW2), list(nuW2), list(muB2), list(nuB2)], (loss, conf)
        carry, (losses, confs) = jax.lax.scan(body, flat, jnp.arange(R))
        return carry, losses, confs
    return f

f100 = make_scan(100)
carry, losses, confs = f100(unpack(clone(state0)))
acc = np.asarray(confs[-1, :, 0, 0] + confs[-1, :, 1, 1]) / np.asarray(confs[-1].sum((1, 2)))

# XLA reference: 100 rounds
xla100 = build_round_fn(mesh, apply_fn, tx, K, rounds_per_step=100)
s_x2, m_x2 = xla100(clone(state0), batch)
acc_x = np.asarray(m_x2["per_client"]["accuracy"])[-1]
print("acc after 100 rounds: fused", acc.mean(), "xla", acc_x.mean())
assert abs(acc.mean() - acc_x.mean()) < 0.01, "trajectory diverged"


flat0 = unpack(clone(state0))
def mk(R):
    f = make_scan(R)
    def run():
        carry, losses, confs = f(flat0)
        return confs[-1].sum()
    return run
m = marginal_slope(mk)
flops = 736897920.0
print(f"fused round marginal: {m*1e6:.2f} us/round -> {flops/m/1e12:.1f} TFLOP/s, {flops/m/158e12*100:.1f}% MFU vs measured peak")
