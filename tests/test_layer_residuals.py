"""What a layer body keeps for its backward, and that keeping less changes
no gradient.

`common.activate` is checkpointed, so a layer saves the MLP's
pre-activations for the backward and recomputes the activation's
elementwise internals from them.  A scanned stack writes every saved
residual into an (L, ...) stack, so each internal saved would cost one
more d_ff-wide stack per round.  These tests run on a tiny scanned dense
config on the CPU.
"""

import contextlib
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from repro.config import reduced
from repro.configs import get_config
from repro.core import rounds, smashed, split
from repro.models import common, transformer
from repro.models.model import build_model

N, B, S, D = 3, 2, 16, 32


def tiny_arch(name="gpt2-small"):
    return reduced(get_config(name), layers=4, d_model=D, vocab=128,
                   seq_len=S, batch=B)


def _round_inputs(model):
    """Round-step state with non-zero LoRA B (zero B leaves dA zero) and
    a random batch, for N clients at the config's cut."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    state = rounds.init_state(model, keys[0], num_clients=N)

    def fill_b(tree, key):
        leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
        ks = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            jax.random.normal(k, v.shape, v.dtype) * 0.1
            if str(path[-1]) == "['B']" else v
            for (path, v), k in zip(leaves, ks)])

    cad = fill_b(state["client_adapters"], keys[1])
    sad = fill_b(state["server_adapters"], keys[2])
    v = model.cfg.vocab_size
    toks = jax.random.randint(keys[3], (N, B, S + 1), 3, v)
    batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:],
             "loss_mask": jnp.ones((N, B, S), jnp.float32)}
    return cad, sad, state["cuts"], batch


def _round_grads(model, params, cad, sad, cuts, batch, compress="int8"):
    """The gradient the round step takes: the weighted per-client loss of
    the cut-split forward, `compress` at each client's cut."""
    boundary = smashed.make_boundary(smashed.make_compressor(compress),
                                     cuts)
    weights = jnp.full((N,), 1.0 / N, jnp.float32)

    def loss_fn(cad_, sad_):
        eff = split.merge_adapters(model, cad_, sad_, cuts)
        per_loss, _ = model.loss(params, eff, batch, per_client=True,
                                 boundary=boundary)
        return jnp.sum(weights * per_loss)

    return jax.jit(jax.grad(loss_fn, argnums=(0, 1)))(cad, sad)


def _assert_rel_close(got, want, rel):
    """Every leaf within `rel` of its reference, normwise."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        err = float(np.linalg.norm(g - w)) / max(
            float(np.linalg.norm(w)), 1e-30)
        assert err <= rel, f"{jax.tree_util.keystr(path)}: {err:.3e}"


def test_scanned_round_grads_match_unrolled():
    """Uncompressed cut: with int8 at the cut, a last-bit difference
    between the two programs can move a value across a quantization step,
    which is the channel's rounding and not the stack's."""
    arch = tiny_arch()
    scanned, unrolled = build_model(arch), build_model(arch, unroll=True)
    assert scanned.group_by_name["dec"].scan
    assert not unrolled.group_by_name["dec"].scan
    params = scanned.init_params(jax.random.PRNGKey(1))
    inputs = _round_inputs(scanned)
    got = _round_grads(scanned, params, *inputs, compress="none")
    want = _round_grads(unrolled, params, *inputs, compress="none")
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(want))
    _assert_rel_close(got, want, 1e-6)


def test_activation_recompute_leaves_round_grads_unchanged(monkeypatch):
    """The checkpointed activation against the plain one, in the scanned
    round: the backward's recompute is the forward's arithmetic."""
    model = build_model(tiny_arch())
    params = model.init_params(jax.random.PRNGKey(1))
    inputs = _round_inputs(model)
    got = _round_grads(model, params, *inputs)
    monkeypatch.setattr(transformer, "activate", common.activate.__wrapped__)
    want = _round_grads(model, params, *inputs)
    _assert_rel_close(got, want, 0.0)


@pytest.mark.parametrize("name,d_ff_saved", [("gpt2-small", 1),
                                             ("llama3-8b", 2)],
                         ids=["gelu", "swiglu"])
def test_layer_body_saves_only_mlp_preactivations(name, d_ff_saved):
    """One layer of the scanned stack saves, d_ff wide, only the MLP's
    pre-activations: `hin` (and the gate branch of a GLU)."""
    model = build_model(tiny_arch(name))
    g = model.group_by_name["dec"]
    assert g.scan
    params = model.init_params(jax.random.PRNGKey(1))
    cad, sad, cuts, _ = _round_inputs(model)
    eff = split.merge_adapters(model, cad, sad, cuts)
    p_l = jax.tree.map(lambda v: v[0], params[g.name])
    ad_l = jax.tree.map(lambda v: v[0], eff[g.name])
    body = model._layer_body(g, policy=common.NO_SHARDING, mode="train",
                             rope=model._rope(jnp.arange(S)), memory=None,
                             window=0)
    x = jax.random.normal(jax.random.PRNGKey(2), (N, B, S, D))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        print_saved_residuals(
            lambda x_, ad_: body(x_, p_l, ad_, None, None)[0], x, ad_l)
    wide = f"f32[{N},{B},{S},{model.cfg.d_ff}]"
    saved = [ln for ln in out.getvalue().splitlines()
             if ln.startswith(wide + " ")]
    assert len(saved) == d_ff_saved, "\n".join(saved)

