"""The plain reference: synchronous SGD with gTop-k sparsification.

Shi et al., "A Distributed Synchronous SGD Algorithm with Global Top-k
Sparsification for Low Bandwidth Networks", arXiv:1901.04359, Alg. 1-4,
written in straightforward ``jax.numpy`` over the frozen model of the
configuration. It imports nothing of ``gtopkssgd_tpu`` and takes nothing the
program made: weights, batches and dropout keys all come from the seed.

Per step and worker w (P workers, N parameters, k = ceil(density * N)):

    g_w    = grad of the worker's loss on its rows     (clipped, if the traffic says so)
    acc_w  = g_w + residual_w                           Alg. 1 line 5: error feedback, float32
    loc_w  = acc_w where |acc_w| is among its k largest Alg. 1 line 6: EXACT top-k
    G      = tree merge of loc_0..loc_{P-1}             Alg. 3: log2 P rounds, peers at
             merge(a, b) = top-k of (a + b)                     distance 1, 2, 4..., cut to k each round
    residual_w = acc_w, zeroed where w's own pick is in G    Alg. 4 lines 6-9: picks the
                                                             tree dropped go back
    update = G / P
    momentum SGD on (update + weight_decay * w)

Departures from the paper, each because the program states the same:
  * momentum and weight decay are applied to the sparse averaged update
    (the paper's Alg. 4 applies plain SGD; its experiments use momentum 0.9);
  * the exact top-k is a bisection for the k-th largest magnitude on the
    float's bit pattern followed by ``|x| >= tau``: exact like a sort (ties at
    tau would all be kept; none occur in float32 gradients), a few passes over N;
  * at P > 1 every chip gathers the P dense accumulators and runs the whole
    tree itself, replicated, in place of the paper's point-to-point rounds:
    the same arithmetic, no sparse wire;
  * BatchNorm uses each worker's own rows and the running statistics are
    averaged over workers after the step, dropout keys are
    fold_in(fold_in(fold_in(PRNGKey(s), step), worker if P > 1), 0), s the
    configuration's ``program.seed``: a constant of the compiled step.

``master_bits=16`` is the control, never a cell: the same algorithm with the
master weights kept in bfloat16, one precision below what the configurations
state, the saving that would tempt a later PR.
"""

import functools
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _refmodel(config):
    return importlib.import_module(
        f"perfbench.refmodels.{config['reference_model']}")


def load_model(config):
    """(module with build/loss/initial_carry, flax module, example input)."""
    ref = _refmodel(config)
    module, example = ref.build(config["sizes"], DTYPES[config["compute_dtype"]])
    return ref, module, example


def init_variables(config, seed):
    """Weights on the device, from the seed, in one jitted call."""
    _, module, example = load_model(config)
    carry = _carry(config, 1)

    def init(key):
        args = (example, carry, False) if carry != () else (example, False)
        return module.init({"params": key, "dropout": key}, *args)

    variables = jax.jit(init)(jax.random.PRNGKey(seed))
    return variables["params"], variables.get("batch_stats", {})


def _carry(config, batch):
    return _refmodel(config).initial_carry(
        config["sizes"], batch, DTYPES[config["compute_dtype"]])


def _stored(params, master_bits):
    """The parameters as the master copy keeps them: float32, or rounded
    to bfloat16 for the control."""
    if master_bits == 32:
        return params
    return jax.tree.map(
        lambda p: p.astype(jnp.bfloat16).astype(jnp.float32), params)


def kth_magnitude(x, k):
    """The k-th largest |x|, exactly: bisection on the bit pattern, which
    orders non-negative floats as integers."""
    bits = lax.bitcast_convert_type(jnp.abs(x), jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo + 1) // 2
        enough = jnp.sum(bits >= mid) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    lo, _ = lax.fori_loop(0, 32, body, (jnp.int32(0), jnp.int32(0x7F800000)))
    return lax.bitcast_convert_type(lo, jnp.float32)


def top_k_dense(x, k):
    """x with everything but its k largest magnitudes zeroed."""
    tau = kth_magnitude(x, k)
    return jnp.where((jnp.abs(x) >= tau) & (x != 0), x, 0.0)


def make_step(config, traffic, master_bits=32):
    """The jitted step: (state, batch) -> (state, loss, sparse update)."""
    ref, module, _ = load_model(config)
    opt = config["optimizer"]
    workers = traffic["chips"]
    dense = traffic["compression"] == "dense"
    lr, momentum, wd = opt["lr"], opt["momentum"], opt["weight_decay"]
    clip = opt.get("clip_grad_norm")

    def worker_step(state, batch):
        params, model_state, carry, trace, residual, step = state
        flat_p, unravel = ravel_pytree(params)
        n = flat_p.shape[0]
        k = max(1, int(np.ceil(traffic.get("density", 1.0) * n)))
        key = jax.random.fold_in(jax.random.PRNGKey(config["program"]["seed"]), step)
        if workers > 1:
            key = jax.random.fold_in(key, lax.axis_index("dp"))
        key = jax.random.fold_in(key, 0)

        def objective(p):
            variables = {"params": p}
            if model_state:
                variables["batch_stats"] = model_state
            value, new_ms, new_carry = ref.loss(
                module, variables, carry, batch, key, True)
            return value, (new_ms if new_ms is not None else model_state,
                           new_carry)

        (loss, (model_state, carry)), grads = jax.value_and_grad(
            objective, has_aux=True)(params)
        g, _ = ravel_pytree(grads)
        if clip is not None:
            g = g * jnp.minimum(1.0, clip / (jnp.sqrt(jnp.sum(g * g)) + 1e-6))
        if dense:
            update = lax.pmean(g, "dp") if workers > 1 else g
        else:
            acc = g + residual
            if workers > 1:
                rows = lax.all_gather(acc, "dp")            # [P, N], replicated
                local = jax.vmap(lambda r: top_k_dense(r, k))(rows)
                merged = local
                while merged.shape[0] > 1:    # Alg. 3: one round, neighbours pair
                    merged = jax.vmap(lambda a, b: top_k_dense(a + b, k))(
                        merged[0::2], merged[1::2])
                total = merged[0]
                mine = local[lax.axis_index("dp")]
            else:
                total = mine = top_k_dense(acc, k)
            delivered = (mine != 0) & (total != 0)
            residual = jnp.where(delivered, 0.0, acc)
            update = total / workers
        trace = momentum * trace + update + wd * flat_p
        params = _stored(unravel(flat_p - lr * trace), master_bits)
        if workers > 1:
            loss = lax.pmean(loss, "dp")
            model_state = jax.tree.map(lambda a: lax.pmean(a, "dp"), model_state)
        return ((params, model_state, carry, trace, residual, step + 1),
                loss, update)

    if workers == 1:
        return jax.jit(worker_step, donate_argnums=0), None

    mesh = Mesh(np.array(jax.devices()[:workers]), ("dp",))
    spec = (P(), P(), P("dp"), P(), P("dp"), P())

    def sharded(state, batch):
        state = state[:2] + (jax.tree.map(lambda a: a[0], state[2]),
                             state[3], state[4][0], state[5])
        state, loss, update = worker_step(
            state, jax.tree.map(lambda a: a[0], batch))
        state = state[:2] + (jax.tree.map(lambda a: a[None], state[2]),
                             state[3], state[4][None], state[5])
        return state, loss, update

    step = jax.shard_map(sharded, mesh=mesh, in_specs=(spec, P("dp")),
                         out_specs=(spec, P(), P()), check_vma=False)
    return jax.jit(step, donate_argnums=0), mesh


def train(config, traffic, seed, batches, steps, *, keep=3, master_bits=32):
    """Run ``steps`` reference steps over ``batches`` (a list of host batches
    with leaves [P, B, ...], step t taking batches[t % len]).

    Returns losses [steps], the flat parameters at steps 0..keep, the sparse
    (or dense) averaged updates of steps 1..keep-1, and the seconds the call
    took."""
    t0 = time.perf_counter()
    workers = traffic["chips"]
    params, model_state = init_variables(config, seed)
    params = _stored(params, master_bits)
    flat, _ = ravel_pytree(params)
    n = flat.shape[0]
    step_fn, mesh = make_step(config, traffic, master_bits)
    carry = _carry(config, traffic["batch_size"])
    residual = jnp.zeros((0 if traffic["compression"] == "dense" else n,),
                         jnp.float32)
    if workers == 1:
        put = jnp.asarray
        strip = functools.partial(jax.tree.map, lambda a: a[0])
    else:
        rep, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
        carry = jax.tree.map(
            lambda a: jax.device_put(jnp.broadcast_to(a, (workers,) + a.shape), dp),
            carry)
        residual = jax.device_put(
            jnp.zeros((workers,) + residual.shape, jnp.float32), dp)
        params, model_state = jax.device_put((params, model_state), rep)
        put = functools.partial(jax.device_put, device=dp)
        strip = lambda b: b
    state = (params, model_state, carry,
             jax.device_put(jnp.zeros((n,), jnp.float32),
                            rep if workers > 1 else None),
             residual, jnp.zeros((), jnp.int32))
    flats, updates, losses = [np.asarray(flat)], [], []
    for t in range(steps):
        batch = jax.tree.map(put, strip(batches[t % len(batches)]))
        state, loss, update = step_fn(state, batch)
        losses.append(loss)
        if t < keep:
            flats.append(np.asarray(ravel_pytree(state[0])[0]))
            if t < keep - 1:
                updates.append(np.asarray(update))
        del update
    losses = [float(x) for x in losses]
    return {"losses": losses, "params": flats, "updates": updates,
            "seconds": time.perf_counter() - t0}
