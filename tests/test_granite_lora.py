"""granite-4.0-h-micro as a federated LoRA payload (``pattern_hybrid``,
``fl_models.LoRAFLModel``) at the SMOKE widths on the CPU: parameter
counts, the blocked head against the whole one, one local epoch, the
scanned horizon against the per-round driver, and the frozen base built
once a process."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FLConfig
from repro.configs import granite_4_0_h_micro as granite
from repro.core import channel as chan
from repro.core import fl, fl_engine
from repro.models import layers as L
from repro.models import pattern_hybrid as P
from repro.models.fl_models import get_fl_model
from repro.models.params import ParamSpec
from repro.utils import spans

SMOKE_NAME = "granite-h-micro-lora:smoke"


def _count(schema):
    leaves = jax.tree_util.tree_leaves(
        schema, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(int(np.prod(s.shape)) for s in leaves)


def test_period_counts_base_and_adapters():
    model = get_fl_model("granite-h-micro-lora")
    assert model.cfg is granite.PERIOD
    assert _count(P.schema(granite.PERIOD)) == 951_991_232
    assert granite.PERIOD.param_count() == 951_991_232
    assert _count(model.schema()) == 1_309_184
    assert granite.CONFIG.param_count() == 3_191_396_096
    assert granite.PERIOD.layer_types == granite.CONFIG.layer_types[:10]
    assert granite.CONFIG.layer_types.count("attention") == 4


def test_smoke_counts_match_the_schema():
    assert _count(P.schema(granite.SMOKE)) == granite.SMOKE.param_count()


def _smoke():
    model = get_fl_model(SMOKE_NAME)
    base = model.frozen_base()
    ad = model.init(jax.random.PRNGKey(1))
    # a trained adapter: B away from zero so the LoRA path carries signal
    ad = jax.tree_util.tree_map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(2),
                                               x.shape), ad)
    return model, base, ad


@pytest.mark.parametrize("block", [7, 32, 512])
def test_blocked_head_matches_the_whole_head(block):
    model, base, ad = _smoke()
    cfg = model.cfg
    x = jax.random.randint(jax.random.PRNGKey(3), (3, 41), 0, cfg.vocab_size)
    labels = x[:, 1:].at[1].set(-1)
    h = P.hidden(base, x[:, :-1], cfg, lora=ad, lora_scale=2.0)
    logits = P.head(base, h, cfg)
    whole = L.cross_entropy(logits, labels, vocab_size=cfg.vocab_size)
    count = jnp.sum(labels >= 0)
    blocked = P.token_nll(base, h, labels, cfg, block=block) / count
    np.testing.assert_allclose(float(blocked), float(whole), rtol=1e-5)
    hits = jnp.sum((jnp.argmax(logits, -1) == labels) & (labels >= 0))
    assert int(P.token_hits(base, h, labels, cfg, block=block)) == int(hits)


def test_loss_reads_the_rows_and_their_labels():
    model, base, ad = _smoke()
    x = jax.random.randint(jax.random.PRNGKey(4), (4, 41), 0, 512)
    y = jnp.array([3, -1, 0, -1])
    got = model.batch_loss((base, ad), x, y, (y >= 0).astype(jnp.float32))
    keep = np.array([0, 2])
    want = model.batch_loss((base, ad), x[keep], y[keep],
                            jnp.ones(2, jnp.float32))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


def test_one_local_epoch_trains_the_adapters_over_an_untouched_base():
    model = get_fl_model(SMOKE_NAME)
    base = model.frozen_base()
    before = jax.tree_util.tree_map(np.array, base)
    ad = model.init(jax.random.PRNGKey(5))
    xb = jax.random.randint(jax.random.PRNGKey(6), (2, 4, 41), 0, 512)
    yb = jnp.array([[0, 1, 2, -1], [3, -1, -1, -1]])
    got = fl_engine.sgd_epoch(ad, xb, yb, 0.5, model=model, base=base)

    want = ad
    for b in range(2):
        valid = (yb[b] >= 0).astype(jnp.float32)
        g = jax.grad(lambda a: model.batch_loss((base, a), xb[b], yb[b],
                                                valid))(want)
        want = jax.tree_util.tree_map(lambda w, d: w - 0.5 * d, want, g)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-9)
    assert any(float(jnp.abs(x).max()) > 0 for x in
               jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                   lambda a, b: a - b, got, ad)))
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(model.frozen_base())):
        np.testing.assert_array_equal(a, np.asarray(b))


def _token_world(m=12, rows=10, seq=41, vocab=512, seed=0):
    rng = np.random.default_rng(seed)
    ds = types.SimpleNamespace(
        x_train=rng.integers(0, vocab, (m * rows, seq)).astype(np.int32),
        y_train=rng.integers(0, 4, m * rows).astype(np.int32),
        x_test=rng.integers(0, vocab, (4, seq)).astype(np.int32),
        y_test=np.zeros(4, np.int32))
    shards = [np.arange(i * rows, (i + 1) * rows) for i in range(m)]
    return ds, shards, chan.CellConfig(num_devices=m)


def _cfg(**kw):
    base = dict(num_devices=12, group_size=3, num_rounds=3, batch_size=5,
                model=SMOKE_NAME, scheduler="lazy-gwmin", power_mode="max",
                compression="adaptive", seed=3)
    return FLConfig(**{**base, **kw})


def test_scanned_horizon_matches_the_per_round_driver():
    ds, shards, cell = _token_world()
    scan = fl.run_federated_learning(
        ds, shards, cell, _cfg(fl_engine="batched", horizon="scan"))
    for engine in ("legacy", "batched"):
        loop = fl.run_federated_learning(ds, shards, cell,
                                         _cfg(fl_engine=engine))
        for a, b in zip(scan.logs, loop.logs):
            assert a.devices == b.devices
            np.testing.assert_array_equal(a.bits, b.bits)
            np.testing.assert_array_equal(a.rates, b.rates)
            assert a.wall_time_s == b.wall_time_s
            assert a.test_accuracy == pytest.approx(b.test_accuracy,
                                                    abs=1e-6)
        for x, y in zip(jax.tree_util.tree_leaves(scan.final_params),
                        jax.tree_util.tree_leaves(loop.final_params)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-4, atol=1e-9)
    # the payload is the adapters alone
    payload = sum(x.size for x in jax.tree_util.tree_leaves(
        scan.final_params))
    assert payload == _count(get_fl_model(SMOKE_NAME).schema())


def test_the_base_is_built_once_and_not_in_the_plan():
    ds, shards, cell = _token_world()
    cfg = _cfg(fl_engine="batched", horizon="scan")
    fl.run_federated_learning(ds, shards, cell, cfg)
    built = spans.counts().get("payload.base_bytes_built", 0)
    assert built > 0
    plan = fl._horizon_setup(ds, shards, cell, cfg, "noma", None)
    assert plan.payload == 32 * _count(get_fl_model(SMOKE_NAME).schema())
    fl.run_federated_learning(ds, shards, cell, cfg)
    assert spans.counts()["payload.base_bytes_built"] == built


def test_the_sharded_sweep_refuses_a_frozen_base():
    with pytest.raises(ValueError, match="frozen base"):
        fl_engine._no_sharded_base({"w": jnp.zeros(1)})
    fl_engine._no_sharded_base(None)


def test_lenet_horizon_traces_the_same_program():
    """``base=None`` adds no operand: LeNet's horizon program is the one
    it was before the frozen-base operand existed."""
    model = get_fl_model("lenet")
    params = model.init(jax.random.PRNGKey(0))
    T, K = 2, 3
    args = (params, jnp.zeros((T, K), jnp.int32), jnp.ones((T, K)),
            jnp.full((T, K), 1 / 3), jnp.zeros((T, K)),
            jnp.zeros((T, 2), jnp.uint32), jnp.ones(T, bool),
            jnp.zeros((T, 1), jnp.int32), jnp.zeros((6, 1, 4, 784)),
            jnp.zeros((6, 1, 4), jnp.int32), jnp.zeros((5, 784)),
            jnp.zeros(5, jnp.int32))
    kw = dict(lr=0.01, epochs=1, payload=32 * 266_610, compress=True,
              paper_exact=False, use_pallas=False, eval_full=True,
              model=model, topk=1.0, ota=False, ota_noise=0.0,
              ota_threshold=0.0, pmax=0.0)

    def core(*a, **k):
        return fl_engine._horizon_core(*a, **kw, **k)

    with_none = jax.make_jaxpr(lambda *a: core(*a, base=None))(*args)
    without = jax.make_jaxpr(lambda *a: core(*a))(*args)
    assert str(with_none) == str(without)
    assert len(with_none.jaxpr.invars) == len(jax.tree_util.tree_leaves(args))
