"""A second model family goes into a checkout as new files only: a family
module, a configuration that names it, a traffic mix and the cell's
entries in ``BENCHMARK.json``. The run then finds all of it by name,
serves it end to end through the same steps as a chip run (``run.run``
with the look for a chip skipped), holds it to its own reference, and
catches an altered token. A configuration that names no family, or a
family with no module, stops the run."""
from __future__ import annotations

import hashlib
import json
import shutil
from pathlib import Path

import pytest

from bench import families
from bench import harness as H
from bench import run as R

DATA = Path(__file__).resolve().parent / "data"
CELL = "tiny-paired.open"
LIMIT = H.load_json(DATA / "tiny_paired.json")["past_near_tie_limit_pct"]


def _files(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of this checkout's benchmark with the new family's files
    added, and the digests of every file that was there before."""
    r = tmp_path_factory.mktemp("checkout")
    shutil.copy(H.ROOT / "BENCHMARK.json", r / "BENCHMARK.json")
    shutil.copytree(H.BENCH, r / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(r)
    shutil.copy(DATA / "paired_family.py", r / "bench" / "families" / "paired.py")
    shutil.copy(DATA / "tiny_paired.json",
                r / "bench" / "configs" / "tiny-paired.json")
    shutil.copy(DATA / "tiny_open.json", r / "bench" / "traffic" / "tiny_open.json")
    b = json.loads((r / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-paired", "source": "test",
                         "file": "bench/configs/tiny-paired.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": CELL, "config": "tiny-paired",
                           "traffic": "tiny_open", "chips": 1,
                           "why": "test"})
    (r / "BENCHMARK.json").write_text(json.dumps(b))
    return r, before


def _run(root, seed=3_000_000_003, seconds=4.0):
    a = R.parse(["--workload", CELL, "--seed", str(seed),
                 "--seconds", str(seconds), "--trace", "0"])
    return R.run(a, require_tpu=False, root=root)


def test_second_family_has_a_weight_tree_of_its_own(checkout):
    root, _ = checkout
    c = H.load_cell(CELL, root).config
    fam = families.of(c)
    assert Path(c[families.FILE_KEY]) == root / "bench" / "families" / "paired.py"
    tree = fam.shapes(c)
    assert set(tree["blocks"]) == {"sub0", "sub1"} and "lm_head" not in tree
    dense = families.of({"family": "dense"})
    assert tree != dense.shapes(c)


def test_second_family_serves_correctly_from_new_files_alone(checkout, capsys):
    root, before = checkout
    out = _run(root)
    err = capsys.readouterr().err
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] == 16
    assert out["check"]["checked_tokens"]["value"] >= H.MIN_CHECKED
    assert "programs built in the window: 0 []" in err
    after = _files(root)
    assert {k: after[k] for k in before if k != "BENCHMARK.json"} == \
        {k: v for k, v in before.items() if k != "BENCHMARK.json"}
    old = json.loads((H.ROOT / "BENCHMARK.json").read_text())
    new = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads"):
        assert new[key][:len(old[key])] == old[key]
    assert {k: v for k, v in new.items() if k not in ("configs", "workloads")} \
        == {k: v for k, v in old.items() if k not in ("configs", "workloads")}


def test_second_family_altered_token_is_caught(checkout, altered_tokens):
    root, _ = checkout
    out = _run(root)
    assert not out["correct"]
    assert out["check"]["past_near_tie_pct"]["value"] > LIMIT


@pytest.mark.parametrize("family", [None, "absent"])
def test_configuration_without_its_family_stops_the_run(tmp_path, family):
    c = H.load_json(DATA / "tiny.json")
    if family is None:
        del c["family"]
    else:
        c["family"] = family
    with pytest.raises(SystemExit) as e:
        families.attach(c, tmp_path, "bench/configs/tiny.json")
    msg = str(e.value)
    assert "bench/configs/tiny.json" in msg and "family" in msg
    if family:
        assert str(tmp_path / "bench" / "families" / "absent.py") in msg
