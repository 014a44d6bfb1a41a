"""Plain reference of the DLRM training step, and the run's initial weights.

Written from the DLRM paper (arXiv:1906.00091) and the MLPerf reference
arguments in the configuration file (``widths`` reads them), independent of the code under test:
a bottom MLP with ReLU after every layer over the packed dense columns,
one embedding row per sparse feature, the dot interaction (pairs strictly
above the diagonal of the Gram matrix of the bottom output and the
embeddings), a top MLP with ReLU between layers and a linear output, and
the mean binary cross-entropy of the logit.  The optimizer is AdamW after
a global-norm clip, as the configuration states.

``init_params`` draws the run's weights from its seed, on the device, in
one jitted call; the system under test is handed the same draw.  The
reference computes in float32 with matmuls at ``highest`` precision;
``dtype=bfloat16`` gives the control, the same steps in bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def widths(config: dict, id_rows: int) -> dict:
    """The model's widths from the configuration's source keys (the DLRM
    reference arguments, with the keys its ``reduced`` lists changed).

    Tables have ``max_ind_range`` ids plus one out-of-vocabulary row; the
    ETL's ids (``id_rows`` of them) have to fit.  The bottom MLP reads the
    packed dense output at its declared padded width: the packer's padding
    columns are zero, so the function is that of the source's
    ``arch_mlp_bot[0]`` inputs.
    """
    bot, top = list(config["arch_mlp_bot"]), list(config["arch_mlp_top"])
    d = config["arch_sparse_feature_size"]
    if config["arch_interaction_op"] != "dot" or config[
            "arch_interaction_itself"]:
        raise ValueError("the reference holds the dot interaction without "
                         "self-pairs only")
    if bot[0] != config["num_dense_features"] or bot[-1] != d:
        raise ValueError(f"arch_mlp_bot {bot} does not run from "
                         f"num_dense_features to arch_sparse_feature_size")
    rows = config["max_ind_range"] + 1
    if id_rows > rows:
        raise ValueError(f"the ETL packs {id_rows} ids, tables hold {rows}")
    outs = {o["name"]: o["cols"] for o in config["etl_outputs"]}
    prec = config["precision"]
    return {"n_dense": bot[0], "n_sparse": config["num_sparse_features"],
            "vocab_size": rows, "d_emb": d, "bot_mlp": bot[1:],
            "top_mlp": top, "dense_padded": outs["dense"],
            "param_dtype": prec["params"], "compute_dtype": prec["compute"]}


def _tn(key, shape, scale):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * scale


def _mlp(key, dims):
    ks = jax.random.split(key, len(dims) - 1)
    return [{"w": _tn(k, (a, b), 1.0 / math.sqrt(a)),
             "b": jnp.zeros((b,), jnp.float32)}
            for k, a, b in zip(ks, dims[:-1], dims[1:])]


def init(key, model):
    """The weights drawn from ``key``: what ``init_params`` jits."""
    f, d = model["n_sparse"], model["d_emb"]
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "tables": _tn(k1, (f, model["vocab_size"], d), 1.0 / math.sqrt(d)),
        "bot_mlp": _mlp(k2, [model["dense_padded"]] + list(model["bot_mlp"])),
        "top_mlp": _mlp(k3, [model["bot_mlp"][-1] + f * (f + 1) // 2]
                        + list(model["top_mlp"])),
    }


def weight_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    return jax.random.key(int(np.random.default_rng(seed).integers(2 ** 31)))


def init_params(seed: int, model: dict):
    """The run's float32 weights, made on the device in one jitted call."""
    return jax.jit(functools.partial(init, model=model))(weight_key(seed))


def _freeze(model: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def loss(params, batch, model):
    f = model["n_sparse"]
    x = batch["dense"]
    for layer in params["bot_mlp"]:
        x = jax.nn.relu(x @ layer["w"] + layer["b"])
    idx = batch["sparse"][:, :f]
    emb = params["tables"][jnp.arange(f)[None, :], idx]      # (B, F, d)
    z = jnp.concatenate([x[:, None, :], emb.astype(x.dtype)], axis=1)
    gram = jnp.einsum("bfd,bgd->bfg", z, z)
    iu, ju = np.triu_indices(f + 1, k=1)
    t = jnp.concatenate([x, gram[:, iu, ju]], axis=1)
    top = params["top_mlp"]
    for i, layer in enumerate(top):
        t = t @ layer["w"] + layer["b"]
        if i < len(top) - 1:
            t = jax.nn.relu(t)
    logit = t[:, 0]
    y = batch["label"].astype(logit.dtype)
    per = (jnp.maximum(logit, 0) - logit * y
           + jnp.log1p(jnp.exp(-jnp.abs(logit))))
    return jnp.mean(per)


def _norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@functools.partial(jax.jit, static_argnames=("model", "opt"),
                   donate_argnums=(0, 1, 2))
def _step(params, m, v, batch, t, *, model, opt):
    model, opt = dict(model), dict(opt)
    lval, g = jax.value_and_grad(loss)(params, batch, model)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                      for x in jax.tree_util.tree_leaves(g)))
    scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gn, 1e-9))
    g = jax.tree_util.tree_map(lambda x: x * scale.astype(x.dtype), g)
    b1, b2 = opt["beta1"], opt["beta2"]
    m = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, a, b):
        step = (a / c1) / (jnp.sqrt(b / c2) + opt["eps"])
        return p - opt["lr"] * (step + opt["weight_decay"] * p)

    params = jax.tree_util.tree_map(upd, params, m, v)
    return params, m, v, lval, _norms(g)


@jax.jit
def _change_norms(p, p0):
    return _norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))


def _by_leaf(tree) -> dict:
    return {jax.tree_util.keystr(k): float(v)
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def train_steps(seed: int, model: dict, opt: dict, batches,
                dtype=jnp.float32) -> dict:
    """Run the steps over ``batches`` (host dicts) from the seed's weights.

    Returns each step's loss, every leaf's norm of the first step's
    gradient as AdamW receives it (after the clip), and every leaf's norm
    of the parameters' change over all the steps.
    """
    frozen_m, frozen_o = _freeze(model), _freeze(opt)
    with jax.default_matmul_precision("highest"):
        p0 = jax.tree_util.tree_map(lambda x: x.astype(dtype),
                                    init_params(seed, model))
        params = jax.tree_util.tree_map(jnp.copy, p0)
        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        v = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for i, b in enumerate(batches):
            dev = {"dense": jnp.asarray(b["dense"], dtype),
                   "sparse": jnp.asarray(b["sparse"]),
                   "label": jnp.asarray(b["label"], dtype)}
            params, m, v, lval, gn = _step(
                params, m, v, dev, jnp.asarray(i + 1, dtype),
                model=frozen_m, opt=frozen_o)
            losses.append(float(lval))
            if grad_norms is None:
                grad_norms = _by_leaf(gn)
        del m, v
        change = _by_leaf(_change_norms(params, p0))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
