"""The granite-4.0-h-micro federated LoRA cell on the CPU, cut to the
program's SMOKE widths: the program's model against the plain reference,
the SSD forms against the step-by-step recurrence, the token world, a tiny
run of the cell through the harness (sound, with each planted fault, and
the bfloat16 control in the program's place), the FLOP count, and the
per-token round-body metric."""
import contextlib
import copy
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, harness, testing, world, xtrace

NAME = "granite-h-micro.fedlora-mapel-gwmin"
# the program's SMOKE (repro.configs.granite_4_0_h_micro) in catalog keys
SMOKE = {"hidden_size": 64, "vocab_size": 512, "num_hidden_layers": 4,
         "layer_types": ["mamba", "mamba", "attention", "mamba"],
         "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
         "mamba_chunk_size": 16, "num_attention_heads": 4,
         "num_key_value_heads": 2, "shared_intermediate_size": 96,
         "intermediate_size": 96, "attention_multiplier": 1 / 16}


def tiny_cell():
    """The cell at SMOKE widths: 24 devices, four rounds, rows of 41 ids,
    and a learning rate of 0.5, so that the adapters' B leaves move about as
    far, against the cell's param_gap limit, as at the cell's size."""
    cell = harness.load_cell(NAME)
    cell.config = copy.deepcopy(cell.config)
    cell.config.update(SMOKE)
    cell.config["fl"].update(num_devices=24, num_rounds=4, learning_rate=0.5,
                             model="granite-h-micro-lora:smoke")
    cell.config["data"].update(train_rows=240, test_rows=4, row_tokens=41)
    return cell


def _run(seed, fault=None):
    cell = tiny_cell()
    jax.clear_caches()
    ctx = testing.FAULTS[fault](cell) if fault else contextlib.nullcontext()
    with ctx:
        return harness.run(cell, seed=seed, seconds=0.0, trace=False,
                           require_tpu=False)


def test_program_logits_match_the_reference():
    """At S = 40 over SSD chunks of 16: the cross-chunk recurrence runs."""
    from repro.models import pattern_hybrid
    from repro.models.fl_models import get_fl_model

    cell = tiny_cell()
    ref = harness.reference(cell)
    dm = ref.dims(cell.config)
    model = get_fl_model(cell.config["fl"]["model"])
    ad = jax.tree_util.tree_map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(1),
                                               a.shape),
        model.init(jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0, 512)
    got = pattern_hybrid.forward(model.frozen_base(), tokens, model.cfg,
                                 lora=ad, lora_scale=2.0)[0]
    base = ref.frozen_base(dm)
    with jax.default_matmul_precision("highest"):
        want = (ref.hidden(base, ad, tokens, dm) @ base["embed"]["tokens"].T
                / dm.logits_scaling)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4 * scale)


def test_ssd_forms_match_the_recurrence():
    from repro.models import mamba2

    ref = harness.reference(tiny_cell())
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    bsz, s, h, p, n = 2, 24, 3, 4, 5
    x = jax.random.normal(keys[0], (bsz, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (bsz, s, h)))
    a_log = jnp.log(jax.random.uniform(keys[2], (h,), minval=1, maxval=4))
    b = jax.random.normal(keys[3], (bsz, s, n))
    c = jax.random.normal(keys[4], (bsz, s, n))
    a = -jnp.exp(a_log)
    state = jnp.zeros((bsz, h, p, n))
    steps = []
    for t in range(s):
        state = (state * jnp.exp(dt[:, t] * a)[..., None, None]
                 + (dt[:, t, :, None] * x[:, t])[..., None]
                 * b[:, t, None, None, :])
        steps.append(jnp.einsum("bhpn,bn->bhp", state, c[:, t]))
    want = np.asarray(jnp.stack(steps, axis=1))
    with jax.default_matmul_precision("highest"):
        whole = ref.ssd_whole(x, dt, a, b, c)
        chunked, _ = mamba2.ssd_chunked(x, dt, a_log, b[:, :, None],
                                        c[:, :, None], chunk=8)
    np.testing.assert_allclose(np.asarray(whole), want, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(chunked), want, rtol=1e-4,
                               atol=1e-5)


def test_token_world_is_made_from_the_seed_with_equal_shards():
    cell = harness.load_cell(NAME)
    a = world.build_world(cell.config, 2**31 + 9)
    b = world.build_world(cell.config, 2**31 + 9)
    c = world.build_world(cell.config, 2**31 + 10)
    data = cell.config["data"]
    assert a.dataset.x_train.shape == (data["train_rows"], 513)
    assert a.dataset.x_test.shape == (data["test_rows"], 513)
    for name in ("x_train", "y_train", "x_test", "y_test"):
        np.testing.assert_array_equal(getattr(a.dataset, name),
                                      getattr(b.dataset, name))
    assert not np.array_equal(a.dataset.x_train, c.dataset.x_train)
    assert a.dataset.x_train.min() >= 0
    assert a.dataset.x_train.max() < cell.config["vocab_size"]
    assert set(np.unique(a.dataset.y_train)) == set(range(data["topics"]))
    assert set(a.sizes.tolist()) == {data["train_rows"]
                                     // cell.config["fl"]["num_devices"]}
    for x, y in zip(a.shards, b.shards):
        np.testing.assert_array_equal(x, y)


def test_tiny_cell_is_correct():
    result = _run(2**31 + 5)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0


@pytest.mark.parametrize("fault", sorted(testing.FAULTS))
def test_fault_fails_the_tiny_cell(fault):
    result = _run(7, fault)
    assert not result["correct"], result["checks"]


def _zeroed_eval(self, params, x, y):
    return jnp.float32(0.0)


def _unshifted_eval(self, params, x, y):
    """Scores each position against its own input id, not the next one."""
    from repro.models import pattern_hybrid

    labels = jnp.where((y >= 0)[:, None], x[:, :-1], -1)
    hits = pattern_hybrid.token_hits(
        params[0], self._hidden(params, x[:, :-1]), labels, self.cfg,
        block=self.block)
    return hits / jnp.maximum(jnp.sum(labels >= 0), 1)


@pytest.mark.parametrize("fault", [_zeroed_eval, _unshifted_eval],
                         ids=["zeroed", "unshifted"])
def test_eval_fault_fails_the_tiny_cell(fault):
    """A planted fault in the program's eval alone: training and plans
    stay sound, so acc_gap has to catch it.  Seed 8's test rows hold one
    hit of the reference in 160 positions, above the cell's limit."""
    from repro.models.fl_models import LoRAFLModel

    cell = tiny_cell()
    jax.clear_caches()
    with testing.patched(LoRAFLModel, "accuracy", fault):
        result = harness.run(cell, seed=8, seconds=0.0, trace=False,
                             require_tpu=False)
    jax.clear_caches()
    assert not result["correct"], result["checks"]
    checks = result["checks"]
    assert checks["acc_gap"]["value"] > checks["acc_gap"]["limit"]
    assert checks["param_gap"]["value"] <= checks["param_gap"]["limit"]


def test_bfloat16_control_fails_the_tiny_cell():
    cell = tiny_cell()
    fl = {**cell.config["fl"], **cell.traffic["fl"]}
    w = world.build_world(cell.config, 11)
    ref = harness.reference(cell)
    control = ref.run_instance(w, fl, cell.config["cell"], 11,
                               dtype=jnp.bfloat16)
    want = harness.run_reference(cell, w, fl, 11, control)
    ok, checks = compare.verdict(compare.numbers(control, want),
                                 cell.spec["limits"])
    assert not ok, checks


def test_sample_flops_is_the_hand_count():
    cell = harness.load_cell(NAME)
    ref = harness.reference(cell)
    d, v, r, f, s = 2048, 100_352, 8, 8192, 512
    d_in, heads, p, n = 4096, 64, 64, 128
    conv_dim = d_in + 2 * n
    in_proj = d_in + conv_dim + heads
    mamba = (d * in_proj + (d + in_proj) * r + d_in * d + (d_in + d) * r
             + 4 * conv_dim + 2 * heads * p * n + 3 * d * f)
    q, kv = 32 * 64, 8 * 64
    attn = (2 * (d * q + (d + q) * r) + 2 * (d * kv + (d + kv) * r)
            + 2 * 32 * 64 * (s + 1) / 2 + 3 * d * f)
    fwd = v * d + 9 * mamba + attn
    lora = 9 * ((d + in_proj) * r + (d_in + d) * r) + 2 * (
        (d + q) * r + (d + kv) * r)
    assert ref.sample_flops(cell.config) == (2 * s * (2 * fwd + lora),
                                             2 * s * fwd)
    assert ref.param_count(cell.config) == lora == 1_309_184
    assert cell.config["model"]["params"] == 1_309_184
    assert cell.config["model"]["base_params"] == 951_991_232


def test_round_body_us_per_token_reads_the_trace():
    E = xtrace.Event
    tr = xtrace.Trace(
        ops=[E("fusion.1", 100, 400, 0, "jit_run_horizon")],
        modules=[E("jit_run_horizon(3)", 100, 400, 0),
                 E("jit__fused_single", 600, 100, 0)],
        spans=[E("bench.call", 0, 1000)], chips=1)
    ctx = types.SimpleNamespace(trace=tr, lo=0, hi=1000, instance_rounds=4)
    metric = harness.load_module(harness.BENCH_DIR / "metrics"
                                 / "round_body_us_per_token.py")
    # a round of the cell: K = 3 devices x one minibatch of 10 rows x 512
    # tokens trained, 10 test rows x 512 tokens evaluated
    tokens = 3 * 10 * 512 + 10 * 512
    assert metric.tokens_per_round(harness.load_cell(NAME).config) == tokens
    assert metric.read(ctx) == pytest.approx(400e-3 / (4 * tokens))
    ctx.instance_rounds = 0
    assert metric.read(ctx) is None
