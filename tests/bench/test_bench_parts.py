"""The parts a traffic mix names reach the program and the reference alike."""

import dataclasses

import numpy as np
import pytest

import bench_tiny

from bench import calibrate, check, harness, spec


def test_federation_defaults_are_the_programs():
    from repro.core import FederationEnv

    fields = {f.name: f.default for f in dataclasses.fields(FederationEnv)}
    for key, value in spec.FEDERATION_DEFAULTS.items():
        assert fields[key] == value, key


@pytest.mark.parametrize("kind,name", [
    ("protocols", "sync"), ("rules", "fedavg"), ("servers", "fedavg"),
    ("codecs", "raw"), ("codecs", "int8"), ("data", "regression"), ("data", "tokens"),
])
def test_every_part_a_cell_names_is_found(kind, name):
    assert spec.part(kind, name).__doc__


def test_a_part_that_is_not_there_is_a_spec_error():
    with pytest.raises(spec.SpecError, match="bench/rules/no_such_rule.py"):
        spec.part("rules", "no_such_rule")


def test_federation_settings_reach_both_sides():
    """A server step of 0.5 in the traffic: the program and the reference both take it."""
    w = bench_tiny.workload(bench_tiny.pick())
    fed = dict(w.traffic["federation"], server_lr=0.5)
    w = dataclasses.replace(w, traffic=dict(w.traffic, federation=fed))
    res = harness.run(w, 9, 0.1, False, require_chip=False, log=lambda m: None)
    assert res["correct"] is True, res["check"]
    # The reference alone at the default step reads the program's half-size change.
    full = dataclasses.replace(w, traffic=dict(w.traffic, federation=dict(fed, server_lr=1.0)))
    values = check.numbers(harness.reference_readings(w, 9), harness.reference_readings(full, 9))
    assert values["update_gap"] == pytest.approx(0.5, abs=0.01)


def test_the_quantized_control_halves_the_codecs_bits():
    import jax.numpy as jnp

    w = bench_tiny.workload(bench_tiny.pick(codec="int8"))
    uplink = calibrate.control_kw(w)["uplink"]
    row = jnp.linspace(-1.0, 1.0, 512, dtype=jnp.float32)
    got = np.asarray(uplink(row)).reshape(2, 256)
    for group in got:
        assert len(np.unique(group)) <= 15  # int4: 7 levels a side and zero
    full = np.asarray(spec.part("codecs", "int8").transmit(row))
    assert len(np.unique(full[:256])) > 15


def test_folding_rows_differs_from_folding_changes_only_in_rounding():
    """On the CPU's float32 matmuls the program's form of FedAvg agrees closely."""
    w = bench_tiny.workload(bench_tiny.pick(family="mlp", codec="raw"))
    (variant, values), = calibrate.readings(w, 4, variants=("rows",))
    assert variant == "rows"
    assert values["update_gap"] < 1e-4 and values["change_gap"] < 1e-3, values


def test_a_refused_draw_is_drawn_again_from_the_seeds_next_key():
    import types

    import jax

    from bench import traffic

    w = bench_tiny.workload(bench_tiny.pick(family="mlp"))
    fam = spec.family(w.family)
    model = fam.program_model(w.config)
    source = traffic.make(w.config, w.traffic, 5)
    seen = []

    def second_only(params, config, traffic, source):
        seen.append(params)
        return len(seen) == 2

    def draw(family):
        return harness.init_params(family, model, w.config, w.traffic, 5, source)

    unchecked = types.SimpleNamespace(abstract_params=fam.abstract_params, init_leaf=fam.init_leaf)
    picky = types.SimpleNamespace(**vars(unchecked), usable=second_only)
    leaves = jax.tree_util.tree_leaves
    first, got = draw(unchecked), draw(picky)
    assert len(seen) == 2
    assert all(bool((a == b).all()) for a, b in zip(leaves(seen[0]), leaves(first)))
    assert not all(bool((a == b).all()) for a, b in zip(leaves(got), leaves(first)))
    assert all(bool((a == b).all()) for a, b in zip(leaves(got), leaves(seen[1])))


@pytest.mark.parametrize("lr,ok", [(1e-3, True), (30.0, False)])
def test_the_mlp_check_refuses_a_draw_whose_federation_blows_up(lr, ok):
    from bench import traffic

    w = bench_tiny.workload(bench_tiny.pick(family="mlp"))
    config = dict(w.config, learning_rate=lr)
    fam = spec.family(w.family)
    source = traffic.make(config, w.traffic, 5)
    unchecked_params = harness.init_params(
        type("F", (), {"abstract_params": staticmethod(fam.abstract_params),
                       "init_leaf": staticmethod(fam.init_leaf)}),
        fam.program_model(config), config, w.traffic, 5, source)
    assert fam.usable(unchecked_params, config, w.traffic, source) is ok
