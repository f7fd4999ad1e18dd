"""``--seed`` makes the traffic and nothing else: the weights are the
configuration's own draw, ``deployment.init_seed``.  Held here: the engine
is handed that key and never the run's seed; two runs at two seeds start
from the same parameters and see different batches; a configuration
without the key fails ``lint`` and refuses to run; and the kernels the two
hybrid configurations require by name are named back when the program's
map lacks one."""
import copy

import jax
import numpy as np
import pytest

from drivers import train_steps
from harness import datagen
from harness.manifest import Manifest, init_seed, lint
from rehearse import rehearse, toy
from test_new_family import run_line

CONFIGS = [c["name"] for c in Manifest().data["configs"]]
# the two cells whose rate follows the draw of the router (PERF.md PR 41)
ROUTED = ["joyai-llm-flash.packed-s8192-gas2",
          "nemotron-3-nano-30b-a3b.packed-s8192-gas2"]


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_states_its_draw(name):
    config = Manifest().config(name)
    seed = config["deployment"]["init_seed"]
    assert isinstance(seed, int) and not isinstance(seed, bool)
    assert init_seed(config) == seed


def test_build_engine_hands_the_engine_the_configurations_draw(monkeypatch):
    import deepspeed_tpu
    handed = []
    monkeypatch.setattr(
        deepspeed_tpu, "initialize",
        lambda model, config, mesh: (handed.append(config) or "engine",))
    _, config, traffic = toy(Manifest(), ROUTED[0])
    config["deployment"]["init_seed"] = 4100000107
    engine, _ = train_steps.build_engine(config, traffic, "model", 3000000001,
                                         jax.devices()[:1])
    assert engine == "engine"
    assert handed[0]["seed"] == 4100000107
    assert 3000000001 not in handed[0].values()


@pytest.mark.parametrize("bad", [None, True, 3.0, "3"])
def test_a_configuration_without_its_draw_is_refused(bad, tmp_path):
    manifest = Manifest()
    cell = manifest.data["workloads"][0]
    config = manifest.config(cell["config"])
    if bad is None:
        del config["deployment"]["init_seed"]
    else:
        config["deployment"]["init_seed"] = bad
    with pytest.raises(SystemExit, match="init_seed"):
        init_seed(config)
    # lint reads the file the manifest names, so the manifest hands it this
    manifest.config = lambda name: copy.deepcopy(config)
    assert any("init_seed" in complaint for complaint in lint(manifest))
    # and the driver refuses before it builds anything
    _, _, traffic = toy(Manifest(), cell["name"])
    with pytest.raises(SystemExit, match="init_seed"):
        train_steps.run_cell(
            cell["name"], config, traffic, {}, 1, 0.1, False,
            jax.devices()[:1], {}, 0.0, str(tmp_path))


def first_batch(traffic, vocab_size, seed):
    stream = datagen.BatchStream(traffic, vocab_size,
                                 traffic["micro_batch_per_chip"], seed)
    try:
        return stream.next()
    finally:
        stream.close()


@pytest.mark.parametrize("cell", ROUTED)
def test_two_seeds_one_draw_two_streams(cell):
    """What two runs at two ``--seed`` values are built from: the same
    parameters bit for bit, other batches; another ``init_seed``, other
    parameters."""
    _, config, traffic = toy(Manifest(), cell)
    model = train_steps.build_model(config)

    def params(seed, draw=None):
        if draw is not None:
            config["deployment"]["init_seed"] = draw
        engine, _ = train_steps.build_engine(config, traffic, model, seed,
                                             jax.devices()[:1])
        return jax.tree.map(np.asarray, engine.state["params"]), \
            train_steps.params_sum(engine)

    (one, sum_one), (two, sum_two) = params(11), params(12)
    assert sum_one == sum_two
    jax.tree.map(np.testing.assert_array_equal, one, two)
    other, sum_other = params(11, draw=config["deployment"]["init_seed"] + 1)
    assert sum_other != sum_one
    vocab = model.config.vocab_size
    a, b = first_batch(traffic, vocab, 11), first_batch(traffic, vocab, 12)
    assert not np.array_equal(a["input_ids"], b["input_ids"])
    np.testing.assert_array_equal(
        a["input_ids"], first_batch(traffic, vocab, 11)["input_ids"])


class HeldPlanTap:
    """The registry tap of ``moe/layer.py`` as two lists: each held expert
    layer's ``moe/held_live_rows`` and each expert's share of the routed
    rows, in the order the device ran the layers."""

    def __init__(self):
        self.live, self.load = [], {}

    def set_gauge(self, name, value, **labels):
        from deepspeed_tpu.moe.layer import HELD_LIVE_ROWS
        if name == HELD_LIVE_ROWS:
            self.live.append(int(value))
        elif name == "moe/expert_load_fraction":
            self.load.setdefault(int(labels["expert"]), []).append(value)

    def inc(self, name, value=1.0, **labels):
        pass


@pytest.mark.parametrize("cell", ROUTED)
def test_the_held_rows_follow_the_draw_not_the_seed(cell):
    """The count the chip's timing follows, where a CPU can show it: the
    rows of the held plan's live prefix in the first micro-batch's forward
    pass (2,048 toy tokens) differ by less between two ``--seed`` values at
    one draw of the weights than between two draws at one ``--seed``, and
    so does the held experts' share of all routed rows."""
    from deepspeed_tpu.moe.layer import set_moe_metrics_registry
    _, config, traffic = toy(Manifest(), cell, seq_len=128, micro=16)
    model = train_steps.build_model(config)
    moe = model.config.moe
    held = range(moe.expert_offset, moe.expert_offset + moe.held)
    forward = jax.jit(model.apply)

    def held_rows(draw, seed):
        config["deployment"]["init_seed"] = draw
        engine, _ = train_steps.build_engine(config, traffic, model, seed,
                                             jax.devices()[:1])
        batch = first_batch(traffic, model.config.vocab_size, seed)
        micro = {k: np.asarray(v)[0] for k, v in batch.items()}
        tap = HeldPlanTap()
        set_moe_metrics_registry(tap)
        try:
            jax.block_until_ready(forward(engine.state["params"], micro))
            jax.effects_barrier()
        finally:
            set_moe_metrics_registry(None)
        return np.array(tap.live), np.sum([tap.load[e] for e in held], 0)

    def apart(a, b):
        return [np.abs(x - y).sum() for x, y in zip(a, b)]

    here = held_rows(0, 11)
    rows_by_seed, share_by_seed = apart(here, held_rows(0, 12))
    rows_by_draw, share_by_draw = apart(here, held_rows(3, 11))
    assert rows_by_seed < rows_by_draw
    assert 3 * share_by_seed < share_by_draw


def test_two_runs_at_two_seeds_print_one_draw(tmp_path, capsys):
    cell = ROUTED[0]
    lines = []
    for seed in (3000000011, 3000000012):
        result = rehearse(cell, seconds=0.2, tmp=str(tmp_path), seed=seed)
        assert result["correct"] is True
        lines.append(run_line(capsys))
    one, two = lines
    assert (one["seed"], two["seed"]) == (3000000011, 3000000012)
    assert one["init_seed"] == two["init_seed"] \
        == Manifest().cell(cell)[1]["deployment"]["init_seed"]
    assert one["initial_params_sum"] == two["initial_params_sum"]
    assert one["first_loss"] != two["first_loss"]
    assert one["reference_loss"] != two["reference_loss"]


@pytest.mark.parametrize("config, dropped", [
    ("nemotron-3-nano-30b-a3b", "ds_ssd_bwd"),
    ("qwen3-next-80b-a3b", "ds_gdr_fwd")])
def test_a_hybrids_kernels_are_required_by_name(config, dropped):
    """The state-space scan, the delta rule and the convolution falling
    back to XLA's form would leave the flash and grouped kernels in the
    step: only the names tell."""
    wanted = Manifest().config(config)["checks"]["require_kernels"]
    assert len(wanted) == 10 and dropped in wanted
    assert {"ds_conv_fwd", "ds_conv_bwd"} <= set(wanted)
    program_map = {f"custom-call.{i}": {"kernel": name}
                   for i, name in enumerate(wanted)}
    assert train_steps.missing_kernels(program_map, wanted) == []
    del program_map[f"custom-call.{wanted.index(dropped)}"]
    assert train_steps.missing_kernels(program_map, wanted) == [dropped]
