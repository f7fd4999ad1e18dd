"""The step's own account of one chip's memory (ISSUE 52): state counted
per device where it is placed, the program's five numbers from the
executable that runs, the summed gradients counted where the step makes
them, the allocator's reading on the fullest device — toy engines on the
CPU's host-device mesh; it is made when somebody asks, and only then."""
import json
import os

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.telemetry import (MemoryLedger, MetricsRegistry,
                                     get_memory_ledger, memory_payload,
                                     peek_step_memory,
                                     reset_memory_ledger, step_memory)
from deepspeed_tpu.telemetry import tracing
from deepspeed_tpu.telemetry.memory import (device_bytes,
                                            hbm_used_fraction)
from tests.util import base_config, random_batch, tiny_gpt2


@pytest.fixture(autouse=True)
def _isolation():
    reset_memory_ledger()
    tracing.reset_programs()
    yield
    reset_memory_ledger()
    tracing.reset_programs()


def _engine(stage, gas=1, steps=1, **config):
    """A toy engine on FOUR of the host's devices, ``steps`` steps in."""
    zero = {"stage": stage}
    if stage == 3:
        zero["param_persistence_threshold"] = 0
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(remat=True),
        mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("data",)),
        config=base_config(gradient_accumulation_steps=gas,
                           zero_optimization=zero, **config))
    one = random_batch(batch_size=4, seq_len=16)
    batch = {k: np.stack([v] * gas) for k, v in one.items()}
    for _ in range(steps):
        engine.train_batch(batch=batch)
    return engine, batch


def _fullest_shards(tree):
    """Bytes on the fullest device, read off the arrays' own shards."""
    per = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) \
                + shard.data.nbytes
    return max(per.values())


def _tree_bytes(tree):
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


def test_zero3_state_is_counted_per_device(devices8):
    engine, _ = _engine(3, steps=0)
    led = get_memory_ledger()
    for owner, tree in (("params", engine.state["params"]),
                        ("optimizer", engine.state["opt_state"])):
        held = led.owner_bytes("device", owner)
        assert held == _fullest_shards(tree)
        # a quarter of the whole tree, to the leaves that cannot be split
        assert _tree_bytes(tree) / 4 <= held < _tree_bytes(tree) / 3
    assert led.owner_bytes("device", "state_other") == _fullest_shards(
        {k: v for k, v in engine.state.items()
         if k not in ("params", "opt_state")})
    # the dtype / int8 split stays as the row's detail, counted whole
    detail = led.snapshot()["tiers"]["device"]["owners"]["params"]["detail"]
    assert detail["plain_bytes"] == _tree_bytes(engine.state["params"])
    # shapes with a sharding count as arrays do: no device read
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        engine.state["params"])
    assert max(device_bytes(abstract).values()) \
        == led.owner_bytes("device", "params")


def test_the_program_is_the_executable_that_runs(devices8):
    engine, batch = _engine(3)
    account = step_memory("train/step")
    analysis = engine.compile_train_step(batch).memory_analysis()
    program = account["program"]
    assert program == {
        "argument": analysis.argument_size_in_bytes,
        "output": analysis.output_size_in_bytes,
        "alias": analysis.alias_size_in_bytes,
        "temp": analysis.temp_size_in_bytes,
        "generated_code": analysis.generated_code_size_in_bytes,
        "peak": analysis.peak_memory_in_bytes}
    assert program["temp"] > 0 and program["alias"] > 0    # state is donated
    held = sum(account["state"].values()) + account["batch"]
    assert account["state"]["params"] == _fullest_shards(
        engine.state["params"])
    assert account["batch"] == 16 * 4       # one device's row of int32 ids
    assert account["layout_padding"] == program["argument"] - held
    # the CPU's peak is its arguments' and a few bytes: it covers no
    # temporaries, and the account has ONE definition of them — None,
    # not the sum of the temporary allocations in their place
    assert program["peak"] - program["argument"] < account["gradients"]
    for key in ("temporaries", "workspace", "expected_peak"):
        assert account[key] is None
    # the CPU's allocator reports nothing: no made-up remainder
    assert account["allocator"] is None and account["unaccounted"] is None
    # the ledger's device tier is fed by whoever makes the account
    led = get_memory_ledger()
    assert led.owner_bytes("device", "gradients") == account["gradients"]
    assert led.owner_bytes("device", "workspace") == 0
    assert step_memory("no/such/program") is None


def _on_a_backend_whose_peak_covers_temporaries(monkeypatch, extra):
    """``program_memory`` as a TPU states it: XLA's peak is the
    arguments, the outputs that alias none and what is live of the
    temporaries — here ``extra`` bytes under their sum."""
    from deepspeed_tpu.runtime import engine as engine_module
    from deepspeed_tpu.telemetry.memory import program_memory

    def with_a_peak(executable):
        program = program_memory(executable)
        return dict(program, peak=program["argument"] + program["output"]
                    - program["alias"] + program["temp"] - extra)
    monkeypatch.setattr(engine_module, "program_memory", with_a_peak)


def test_the_stated_sums_where_the_peak_covers_temporaries(monkeypatch,
                                                           devices8):
    _on_a_backend_whose_peak_covers_temporaries(monkeypatch, extra=64)
    _engine(2)
    account = step_memory("train/step")
    program = account["program"]
    held = sum(account["state"].values()) + account["batch"]
    assert account["temporaries"] == program["temp"] - 64
    assert account["workspace"] + account["gradients"] \
        == account["temporaries"]
    assert account["expected_peak"] == held + program["output"] \
        - program["alias"] + account["temporaries"] \
        + program["generated_code"]
    assert account["expected_peak"] - account["layout_padding"] \
        == program["peak"] + program["generated_code"]
    led = get_memory_ledger()
    assert led.owner_bytes("device", "workspace") == account["workspace"]
    detail = led.snapshot()["tiers"]["device"]["owners"]["workspace"]
    assert detail["detail"] == program


@pytest.mark.parametrize("stage,gas,accum", [
    (0, 1, "fp32"), (0, 4, "bf16"), (2, 1, "bf16"), (2, 4, "fp32")])
def test_gradients_are_the_summed_tree_as_laid_out(stage, gas, accum,
                                                   devices8):
    engine, _ = _engine(stage, gas, bf16={"enabled": True},
                        data_types={"grad_accum_dtype": accum})
    itemsize = {"fp32": 4, "bf16": 2}[accum]
    params = jax.tree.leaves(engine.state["params"])
    whole = sum(p.size for p in params) * itemsize
    by_hand = sum(
        int(np.prod(sharding.shard_shape(p.shape))) * itemsize
        for p, sharding in zip(params, jax.tree.leaves(engine.grad_shardings)))
    account = step_memory("train/step")
    assert account["gradients"] == by_hand == tracing.gradient_bytes()
    if stage == 0:
        assert by_hand == whole             # whole on every device
    else:
        assert whole / 4 <= by_hand < whole / 3
    # among the temporaries: the sum of their allocations holds it
    assert account["gradients"] < account["program"]["temp"]


def test_temporaries_are_what_is_live_at_the_programs_peak(monkeypatch):
    """On a TPU the runtime reserves XLA's peak less the arguments, not
    the sum of the temporary allocations: cell 1's own numbers (my chip
    run, PR 52), where ``peak_bytes_reserved`` read 4,691,738,624."""
    from deepspeed_tpu.telemetry import memory
    program = {"argument": 6082546688, "output": 6082501632,
               "alias": 6082497536, "temp": 5601700352,
               "generated_code": 24383488, "peak": 10770180096}
    live = memory.live_temporaries(program, 1520600064)
    assert live == 10770180096 - 6082546688 - 4096 < program["temp"]
    counted = {"state": {"params": 1520600064, "optimizer": 4561800196,
                         "state_other": 20},
               "batch": 49152, "program": program, "gradients": 1520600064,
               "temporaries": live}
    monkeypatch.setattr(memory, "get_program_memory",
                        lambda name, create=True: counted)
    account = step_memory("train/step")
    assert account["workspace"] == live - 1520600064
    assert account["expected_peak"] == 10770180096 + 24383488 \
        - account["layout_padding"]
    # a peak that leaves less than the gradients covers no temporaries
    # (the CPU's), and a step not known to hold a gradient tree has
    # nothing to hold its peak against: None, and never the sum of the
    # temporary allocations in their place
    assert memory.live_temporaries(dict(program, peak=6082550855),
                                   1520600064) is None
    assert memory.live_temporaries(program, None) is None
    counted.update(gradients=None, temporaries=None)
    account = step_memory("train/step")
    assert account["workspace"] is None and account["unaccounted"] is None
    assert account["expected_peak"] is None
    assert account["program"]["temp"] == 5601700352     # still to be read


def _opened(name):
    return sum(s["name"] == name for s in tracing.setup_account()["spans"])


def test_nobody_asking_nothing_is_loaded(devices8):
    _engine(2, steps=4)
    assert _opened(tracing.SPAN_FUSED_STEP) == 4
    for name in (tracing.SPAN_MEMORY_COMPILED, tracing.SPAN_COMPILE_AOT,
                 tracing.SPAN_PROGRAM_TEXT):
        assert _opened(name) == 0
    assert get_memory_ledger().owner_bytes("device", "workspace") == 0
    # the map and the account, one after the other: one load for both
    assert tracing.get_program_map("train/step")
    assert step_memory("train/step")["program"]["temp"] > 0
    assert _opened(tracing.SPAN_COMPILE_AOT) == 1
    assert _opened(tracing.SPAN_PROGRAM_TEXT) == 1
    assert _opened(tracing.SPAN_MEMORY_COMPILED) == 1
    step_memory("train/step"), tracing.get_program_map("train/step")
    assert _opened(tracing.SPAN_COMPILE_AOT) == 1


def test_the_account_alone_keeps_no_text(devices8):
    """The account asked first — and in production alone — keeps six
    numbers and not the step's text (tens of MB for the engine's life):
    a map asked for later is a load of its own."""
    _engine(2)
    assert step_memory("train/step")["gradients"] > 0
    assert step_memory("train/step")["gradients"] > 0
    assert _opened(tracing.SPAN_COMPILE_AOT) == 1
    assert tracing.get_program_map("train/step")
    assert _opened(tracing.SPAN_COMPILE_AOT) == 2
    assert "ENTRY" in tracing.get_program_text("train/step")


def test_a_backend_without_the_analysis_is_asked_once(monkeypatch,
                                                      devices8):
    from deepspeed_tpu.runtime import engine as engine_module
    monkeypatch.setattr(engine_module, "program_memory",
                        lambda executable: None)
    _engine(2)
    assert step_memory("train/step") is None
    assert step_memory("train/step") is None
    assert _opened(tracing.SPAN_COMPILE_AOT) == 1


def test_two_askers_at_once_make_one_load(devices8):
    import threading
    _engine(2)
    accounts = []
    askers = [threading.Thread(
        target=lambda: accounts.append(step_memory("train/step")))
        for _ in range(2)]
    for t in askers:
        t.start()
    for t in askers:
        t.join(timeout=240)
    assert len(accounts) == 2 and accounts[0] == accounts[1] is not None
    assert _opened(tracing.SPAN_COMPILE_AOT) == 1


def test_a_ledger_that_is_off_has_no_account(devices8):
    _engine(2, telemetry={"memory": False})
    assert step_memory("train/step") is None
    assert _opened(tracing.SPAN_COMPILE_AOT) == 0
    assert get_memory_ledger().tier_bytes("device") == 0


class _TwoDevices:
    """An accelerator whose second device is the fuller one."""
    stats = [{"bytes_in_use": 300, "peak_bytes_in_use": 320,
              "bytes_reserved": 100, "peak_bytes_reserved": 150,
              "bytes_limit": 1000},
             {"bytes_in_use": 310, "peak_bytes_in_use": 330,
              "bytes_reserved": 440, "peak_bytes_reserved": 460,
              "bytes_limit": 1000}]

    def local_device_count(self):
        return len(self.stats)

    def memory_stats(self, device_index=0):
        return self.stats[device_index]


@pytest.fixture
def accelerator():
    from deepspeed_tpu.accelerator import get_accelerator, set_accelerator
    real = get_accelerator()
    yield set_accelerator
    set_accelerator(real)


def test_the_fraction_is_the_fullest_devices_sum(accelerator):
    accelerator(_TwoDevices())
    led, reg = MemoryLedger(), MetricsRegistry()
    led.publish(reg)
    assert reg.get_gauge("mem/hbm_used_bytes") == 310 + 440
    assert reg.get_gauge("mem/hbm_used_fraction") == 0.75
    assert hbm_used_fraction() == 0.75
    dev = led.snapshot()["device_stats"]
    assert dev["used_bytes"] == 750 and dev["used_fraction"] == 0.75
    assert dev["watermark_bytes"] == 330 + 460
    # the watermark follows whichever device peaks highest
    _TwoDevices.stats[0]["peak_bytes_reserved"] = 700
    try:
        led.observe_device()
        assert led.snapshot()["device_stats"]["watermark_bytes"] == 320 + 700
    finally:
        _TwoDevices.stats[0]["peak_bytes_reserved"] = 150

    class _InUseOnly:           # a backend that reports no reservations
        def memory_stats(self, device_index=0):
            return {"bytes_in_use": 600, "bytes_limit": 1000}

    accelerator(_InUseOnly())
    assert hbm_used_fraction() == 0.6
    assert MemoryLedger().snapshot()["device_stats"]["watermark_bytes"] == 600


def test_a_backend_without_statistics_publishes_no_fraction(accelerator):
    class _NoStats:
        def local_device_count(self):
            return 2

        def memory_stats(self, device_index=0):
            return {}

    accelerator(_NoStats())
    led, reg = MemoryLedger(), MetricsRegistry()
    led.publish_and_feed(reg)
    assert reg.get_gauge("mem/hbm_used_fraction") is None
    assert reg.get_gauge("mem/hbm_used_bytes") is None
    assert hbm_used_fraction() is None
    assert "device_stats" not in led.snapshot()


def test_the_allocator_joins_the_account(accelerator, monkeypatch,
                                         devices8):
    _on_a_backend_whose_peak_covers_temporaries(monkeypatch, extra=0)
    _engine(2)
    accelerator(_TwoDevices())
    account = step_memory("train/step")
    assert account["allocator"] == {"peak_bytes_in_use": 330,
                                    "peak_bytes_reserved": 460,
                                    "bytes_limit": 1000}
    assert account["unaccounted"] == 330 + 460 - account["expected_peak"]


def test_debug_memory_and_the_bundle_peek_and_never_ask(tmp_path,
                                                        devices8):
    """A read-only GET and a crashing process start no load of the
    step's executable and write no ledger row: they show the account
    once somebody has asked for it."""
    from deepspeed_tpu.resilience.postmortem import (reset_rate_limit,
                                                     write_postmortem)
    assert memory_payload()["step"] is None         # no step has run
    _engine(2)
    reset_rate_limit()
    bundle = write_postmortem(str(tmp_path / "before"), "test",
                              min_interval_s=0)
    with open(os.path.join(bundle, "memory.json")) as f:
        assert json.load(f)["step"] is None
    assert memory_payload()["step"] is None and peek_step_memory() is None
    for name in (tracing.SPAN_MEMORY_COMPILED, tracing.SPAN_COMPILE_AOT):
        assert _opened(name) == 0
    assert "gradients" not in memory_payload()["tiers"]["device"]["owners"]
    # ask first, then read
    account = step_memory("train/step")
    payload = memory_payload()
    assert payload["step"] == account == peek_step_memory()
    owners = payload["tiers"]["device"]["owners"]
    assert owners["gradients"]["bytes"] == account["gradients"]
    reset_rate_limit()
    bundle = write_postmortem(str(tmp_path / "after"), "test",
                              min_interval_s=0)
    with open(os.path.join(bundle, "memory.json")) as f:
        assert json.load(f)["step"] == account
    assert _opened(tracing.SPAN_COMPILE_AOT) == 1
