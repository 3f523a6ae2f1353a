"""Serving engine invariants (continuous batching over an adapter pool).

The contract: batched multi-adapter decode through the engine produces
EXACTLY the tokens of per-request, single-adapter serial decode — across
heterogeneous adapter ranks, adapter-id permutations, slot churn, and
request mixes — and does it in one traced decode executable.

tier-1 runs these on the jnp oracle dispatch; the kernels-interpret CI
lane re-runs the same tests with REPRO_PALLAS_INTERPRET=1 so the indexed
LoRA kernel and the (paged) flash-decode kernel are exercised too.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from hypothesis_compat import given, settings, st

from repro.config import reduced
from repro.configs import get_config
from repro.kernels.lora_matmul import ops as lora_ops
from repro.kernels.lora_matmul import ref as lora_ref
from repro.models.model import build_model
from repro.runtime import kv_cache, serving


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def setup():
    arch = reduced(get_config("gpt2-small"), d_model=32, vocab=256,
                   seq_len=16)
    model = build_model(arch)
    params = model.init_params(jax.random.PRNGKey(0))
    # heterogeneous effective ranks across the pool — masked rank slots,
    # the same idiom as state["rank_cut"] in training
    pool = serving.build_adapter_pool(model, jax.random.PRNGKey(1), 3,
                                      ranks=[4, 2, 4])
    return model, params, pool


def _requests(rng, n, n_adapters, *, max_plen=10, max_new=4):
    return [serving.Request(
        rid=i, adapter=int(rng.integers(0, n_adapters)),
        tokens=rng.integers(3, 250, size=int(rng.integers(2, max_plen))),
        max_new=int(rng.integers(1, max_new + 1))) for i in range(n)]


# ---------------------------------------------------------------------------
# Op level: indexed multi-adapter LoRA == per-row single-adapter LoRA


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_indexed_lora_matches_per_row(dtype):
    p, b, s, k, n, r = 4, 5, 3, 32, 48, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(ks[0], (b, s, k), dtype)
    w = (jax.random.normal(ks[1], (k, n)) * 0.05).astype(dtype)
    a_pool = (jax.random.normal(ks[2], (p, k, r)) * 0.05).astype(dtype)
    b_pool = (jax.random.normal(ks[3], (p, r, n)) * 0.05).astype(dtype)
    # heterogeneous ranks via masked slots (adapter i keeps rank ranks[i])
    ranks = jnp.asarray([8, 2, 4, 8])
    mask = (jnp.arange(r)[None, :] < ranks[:, None]).astype(dtype)
    a_pool = a_pool * mask[:, None, :]
    b_pool = b_pool * mask[:, :, None]
    scale = jnp.asarray([0.5, 2.0, 1.0, 0.25], jnp.float32)
    ids = jnp.asarray([2, 0, 3, 0, 1], jnp.int32)

    got = lora_ops.lora_matmul_indexed(x, w, a_pool, b_pool, scale, ids)
    for i in range(b):
        aid = int(ids[i])
        want = lora_ref.lora_matmul(x[i], w, a_pool[aid], b_pool[aid],
                                    scale[aid])
        np.testing.assert_allclose(np.asarray(got[i], np.float32),
                                   np.asarray(want, np.float32),
                                   **tol(dtype))


@given(perm=st.permutations(list(range(5))))
@settings(max_examples=10, deadline=None)
def test_indexed_lora_id_permutation_property(perm):
    """Permuting rows and their adapter ids together permutes the output:
    adapter selection is genuinely per-row, with no cross-row coupling."""
    p, b, k, n, r = 3, 5, 16, 24, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (b, k))
    w = jax.random.normal(ks[1], (k, n)) * 0.05
    a_pool = jax.random.normal(ks[2], (p, k, r)) * 0.05
    b_pool = jax.random.normal(ks[3], (p, r, n)) * 0.05
    scale = jnp.asarray([1.0, 0.5, 2.0], jnp.float32)
    ids = jnp.asarray([0, 2, 1, 0, 2], jnp.int32)
    perm = jnp.asarray(list(perm), jnp.int32)

    out = lora_ops.lora_matmul_indexed(x, w, a_pool, b_pool, scale, ids)
    out_p = lora_ops.lora_matmul_indexed(x[perm], w, a_pool, b_pool,
                                         scale, ids[perm])
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out[perm]),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Engine level: batched continuous decode == serial oracle


@pytest.mark.parametrize("page_size", [0, 8])
def test_engine_matches_serial(setup, page_size):
    model, params, pool = setup
    rng = np.random.default_rng(4)
    reqs = _requests(rng, 6, 3)
    want = serving.serial_reference(model, params, pool, reqs, max_len=24)
    eng = serving.ServingEngine(
        model, params, pool,
        serving.ServeConfig(num_slots=3, max_len=24, page_size=page_size))
    res = eng.run(reqs)
    for r in res:
        assert r["tokens"] == want[r["rid"]], (page_size, r)
        assert r["t_done"] is not None and r["t_first"] is not None
    assert eng.decode_traces["n"] == 1


def test_engine_single_trace_across_request_mixes(setup):
    """Admissions, completions, adapter switches, staggered arrivals, and
    slot reuse all ride ONE decode executable — slot state is data."""
    model, params, pool = setup
    eng = serving.ServingEngine(
        model, params, pool,
        serving.ServeConfig(num_slots=2, max_len=24, page_size=8))
    rng = np.random.default_rng(5)
    # more requests than slots, mixed adapters/lengths, staggered arrivals
    reqs = _requests(rng, 7, 3)
    for i, r in enumerate(reqs):
        r.arrival = 0.002 * i
    res = eng.run(reqs)
    assert len(res) == 7 and all(r["tokens"] for r in res)
    assert eng.decode_traces["n"] == 1
    # prefill compiles per bucket, not per request
    buckets = {eng.bucket_for(r["prompt_len"]) for r in res}
    assert eng.prefill_traces["n"] == len(buckets)


def test_engine_pool_permutation_invariance(setup):
    """Permuting the pool rows (and relabeling request adapter ids to
    match) leaves every generation identical."""
    model, params, pool = setup
    rng = np.random.default_rng(6)
    reqs = _requests(rng, 5, 3)
    base = serving.ServingEngine(
        model, params, pool, serving.ServeConfig(num_slots=2, max_len=24))
    want = {r["rid"]: r["tokens"] for r in base.run(reqs)}

    perm = [2, 0, 1]                      # new row j = old row perm[j]
    inv = {old: new for new, old in enumerate(perm)}
    pool_p = jax.tree.map(lambda v: v[:, jnp.asarray(perm)], pool)
    reqs_p = [serving.Request(rid=r.rid, adapter=inv[r.adapter],
                              tokens=r.tokens, max_new=r.max_new)
              for r in reqs]
    eng = serving.ServingEngine(
        model, params, pool_p,
        serving.ServeConfig(num_slots=2, max_len=24))
    for r in eng.run(reqs_p):
        assert r["tokens"] == want[r["rid"]]


def test_latency_stamps_follow_the_engine_clock(setup):
    """t_submit is when the request was due, t_first when its first token
    reached the host after the prefill, t_done when the tick that made
    its last token returned; the injected clock advances 1 s per prefill
    and 10 s per decode tick."""
    from repro.runtime import spans
    model, params, pool = setup
    clock = {"t": 100.0}
    eng = serving.ServingEngine(
        model, params, pool, serving.ServeConfig(num_slots=1, max_len=16),
        clock=lambda: clock["t"])

    def advancing(fn, dt):
        def call(*a):
            out = fn(*a)
            clock["t"] += dt
            return out
        return call

    eng._prefill = advancing(eng._prefill, 1.0)
    eng._decode = advancing(eng._decode, 10.0)
    reqs = [serving.Request(rid=0, adapter=0, tokens=np.arange(3, 7),
                            max_new=3, arrival=0.0),
            serving.Request(rid=1, adapter=1, tokens=np.arange(5, 9),
                            max_new=2, arrival=5.0)]
    spans.reset()
    res = eng.run(reqs)
    stamps = [(r["t_submit"], r["t_first"], r["t_done"]) for r in res]
    # rid 0: due 0, prefill ends at 1, its third token at 1 + 2 ticks;
    # rid 1: due at 5 though first seen at 11, admitted when rid 0 frees
    # its slot at 21, prefill ends at 22, its second token at 32
    assert stamps == [(0.0, 1.0, 21.0), (5.0, 22.0, 32.0)]
    assert res[0]["tokens"] and len(res[1]["tokens"]) == 2
    recs = spans.records()
    names = {r.extra["id"]: r.name for r in recs}
    pairs = {(r.name, names.get(r.extra["parent"])) for r in recs
             if r.name.startswith("serve.")}
    assert pairs == {("serve.admit", None), ("serve.tick", None),
                     ("serve.prefill", "serve.admit"),
                     ("serve.wait.tick", "serve.tick")}
    assert sorted(r.extra["rid"] for r in recs
                  if r.name == "serve.prefill") == [0, 1]
    spans.reset()


def test_serve_cli_prints_host_time_by_span(capsys):
    """`repro.launch.serve` reports the engine's spans: admission with its
    prefills, ticks with their waits, one prefill per request."""
    from repro.launch import serve as serve_cli
    assert serve_cli.main(["--reduced", "--adapters", "2", "--requests",
                           "3", "--num-slots", "2", "--prompt-len", "8",
                           "--gen", "3"]) == 0
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("host time: ")]
    assert len(line) == 1
    parts = {}
    for part in line[0][len("host time: "):].split(", "):
        name, rest = part.split(" ", 1)
        parts[name] = int(rest.rsplit(" over ", 1)[1])
    assert set(parts) == {"serve.admit", "serve.prefill", "serve.tick",
                          "serve.wait.tick"}
    assert parts["serve.prefill"] == 3
    assert parts["serve.tick"] == parts["serve.wait.tick"] >= 3


# ---------------------------------------------------------------------------
# Slot churn: free/admit round-trip is surgical


def test_free_admit_leaves_other_slots_bit_identical(setup):
    model, params, pool = setup
    ps, max_len = 8, 24
    cache = kv_cache.init_paged_cache(model, 3, max_len, ps)
    alloc = kv_cache.PageAllocator(kv_cache.default_num_pages(
        3, max_len, ps))
    p_max = kv_cache.pages_per_slot(max_len, ps)

    def random_temp(seed, bucket):
        temp = model.init_cache((1,), bucket)
        leaves, treedef = jax.tree_util.tree_flatten(temp)
        ks = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
        leaves = [jax.random.normal(k, leaf.shape, leaf.dtype)
                  if jnp.issubdtype(leaf.dtype, jnp.floating) else leaf
                  for leaf, k in zip(leaves, ks)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    pages = {}
    for slot in range(3):
        pages[slot] = alloc.alloc(2)
        row = jnp.asarray(kv_cache.page_row(pages[slot], p_max))
        cache = kv_cache.install_slot_paged(
            cache, slot, random_temp(slot, 16), row, 10 + slot)

    def snapshot(c, slots):
        view = kv_cache.gather_contiguous(c)
        sl = jnp.asarray(slots)
        return jax.tree.map(
            lambda v: np.asarray(v[:, sl]) if v.ndim >= 2
            else np.asarray(v[sl]), view)

    before = snapshot(cache, [1, 2])
    before_tables = np.asarray(cache["pages"][1:])

    # churn slot 0: free, recycle its pages into a new install
    cache = kv_cache.free_slot(cache, 0)
    alloc.free(pages[0])
    new_pages = alloc.alloc(3)
    row = jnp.asarray(kv_cache.page_row(new_pages, p_max))
    cache = kv_cache.install_slot_paged(cache, 0, random_temp(9, 24),
                                        row, 20)

    after = snapshot(cache, [1, 2])
    for b, a in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(b, a)    # bit-identical
    np.testing.assert_array_equal(before_tables,
                                  np.asarray(cache["pages"][1:]))


# ---------------------------------------------------------------------------
# Guards (satellites: loud capacity failure, valid adapter ids)


def test_capacity_guard_raises_loudly(setup):
    model, params, pool = setup
    eng = serving.ServingEngine(
        model, params, pool, serving.ServeConfig(num_slots=1, max_len=16))
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(serving.Request(rid=0, adapter=0,
                                   tokens=np.arange(3, 15), max_new=10))
    with pytest.raises(ValueError, match="adapter"):
        eng.submit(serving.Request(rid=1, adapter=7,
                                   tokens=np.arange(3, 7), max_new=2))
    with pytest.raises(ValueError, match="max_new"):
        eng.submit(serving.Request(rid=2, adapter=0,
                                   tokens=np.arange(3, 7), max_new=0))


def test_serve_cli_parser_has_serving_knobs():
    from repro.launch import serve
    opts = {a.option_strings[0] for a in serve.build_parser()._actions
            if a.option_strings}
    assert {"--adapters", "--requests", "--arrival-rate", "--num-slots",
            "--page-size", "--max-len"} <= opts
