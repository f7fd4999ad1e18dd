"""Every configuration of the benchmark names the draw of its weights: an
integer ``deployment.init_seed`` (benchmarks/README.md "Adding things": the
engine's ``seed`` is that and never ``--seed``; a file without the key
refuses to run).  One case a file, so that a new configuration is held to
it the day it is added."""
import glob
import json
import os

import pytest

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "configs")
FILES = sorted(os.path.basename(p)
               for p in glob.glob(os.path.join(CONFIGS, "*.json")))


def test_the_benchmark_has_its_configurations():
    assert len(FILES) >= 11
    assert "kimi-linear-48b-a3b.json" in FILES
    with open(os.path.join(os.path.dirname(CONFIGS), "..",
                           "BENCHMARK.json")) as f:
        named = {os.path.basename(c["file"])
                 for c in json.load(f)["configs"]}
    assert named == set(FILES)


@pytest.mark.parametrize("name", FILES)
def test_a_configuration_names_its_draw(name):
    with open(os.path.join(CONFIGS, name)) as f:
        config = json.load(f)
    seed = config["deployment"]["init_seed"]
    assert type(seed) is int and 0 <= seed < 2 ** 32, seed
