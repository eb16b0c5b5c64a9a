"""The benchmark's seam, collected for tier-1: the cases of
``benchmark/tests/test_seam.py`` (pinned digests and costs of the Llama
family, every metric file's reader, a fixture family through generator,
hub, reference and ``run.py`` up to the engine) and of
``benchmark/tests/test_exaone_moe_family.py``,
``test_qwen3_next_family.py``, ``test_phi4flash_family.py``,
``test_axk1_family.py``, ``test_longcat_flash_family.py`` and
``test_zaya_family.py`` but their rehearsed runs, which take minutes. The files stay where the benchmark keeps
them; this module only gives them a name under ``tests/``.

Two cases are replaced, because a file the benchmark already has is not
this kind of PR's to edit. The seam's test of an unknown ``model_type``
names ``exaone-moe``, which has had its family file since PR 28. And
``test_axk1_family.py`` holds that ``axk1-reason`` is the last name of every
list it is in, which was true until PR 44 appended a cell after it: it runs
here on ``BENCHMARK.json`` as its PR left it (the cells up to its own, every
list in the order it has), and what it meant is held for every cell at once
by ``test_longcat_flash_family.py`` (each list in the order of
``workloads``).
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import pytest
from _pytest.fixtures import FixtureFunctionDefinition

_TESTS = Path(__file__).resolve().parent.parent / "benchmark" / "tests"


def _cases_of(name: str) -> dict:
    spec = importlib.util.spec_from_file_location(
        f"benchmark_tests_{name}", _TESTS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return {k: v for k, v in vars(module).items()
            if k.startswith("test_")
            or isinstance(v, FixtureFunctionDefinition)}


globals().update(_cases_of("test_seam"))
for _family in ("test_exaone_moe_family", "test_qwen3_next_family",
                "test_phi4flash_family", "test_axk1_family",
                "test_longcat_flash_family", "test_zaya_family"):
    globals().update({k: v for k, v in _cases_of(_family).items()
                      if "rehears" not in k})


def test_unknown_model_type_names_the_file_to_add():  # noqa: F811
    from lib import families

    with pytest.raises(ValueError, match=r"lib/families/state_space\.py"):
        families.of({"model_type": "state-space"})
    with pytest.raises(ValueError, match="no model_type"):
        families.of({"hidden_size": 8})


_axk1_cell = test_axk1_cell_is_in_every_list_it_was_promised  # noqa: F821


def test_axk1_cell_is_in_every_list_it_was_promised(  # noqa: F811
        tmp_path, monkeypatch):
    module = sys.modules[_axk1_cell.__module__]
    bench = json.loads((module.BENCH.parent / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    later = set(cells[cells.index(module.CELL) + 1:])
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] not in later]
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "workloads" in m:
                m["workloads"] = [c for c in m["workloads"]
                                  if c not in later]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "benchmark").symlink_to(module.BENCH)
    monkeypatch.setattr(module, "BENCH", tmp_path / "benchmark")
    _axk1_cell()
