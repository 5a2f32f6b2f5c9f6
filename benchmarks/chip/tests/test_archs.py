"""Self-checks of the architecture a configuration brings, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

A configuration names its architecture by its published ``model_type``; the
harness finds ``archs/<model_type>.py`` by that name.  These check that the
Llama module draws, computes and counts bit for bit what the harness did
before it was found by name (digests recorded then), that it defines the
interface, and, in a copy of the benchmark's tree, that a configuration
whose module is missing fails by name while one whose module is a renamed
copy rehearses end to end: a new architecture needs only new files.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}

# ``tests/digests.py`` on the tree before the architecture moved into
# ``archs/llama.py`` (the names are its keys)
GOLDEN = {
    "weights.small.7": "c85dd72191d03b9679980e00034753645bdca776ea7557d80208761d510d4aa4",
    "gaps.small.7": "9550500a5f3c3bd7a1c2673d9d2d25fd20145b15e6ccb21ca57c0aa2eaf37618",
    "weights.small.2147495993": "72563ac99982dc64fed109b444af66f0707e613d3b5a158e38c8f32ac693a2a3",
    "gaps.small.2147495993": "b852167d6e340e1b08d4bcbb1fb70dba7f239f0815358f84bfb353e09e0881ee",
    "work.small": "b350e9482ebfb2426192305ce53d54c10431efe5e47d8efa5ece54da85b1305c",
    "weights.smoke.7": "967ea43207e1882c0fbbf2f79c17f8ca5d4d9b2c7c2710bb4d9b3c75aa9e8459",
    "gaps.smoke.7": "9926e6dbcfe317280e29d856b0bfba85dda140aace1c51d979adc34ce31354fa",
    "weights.smoke.2147495993": "360527e0e312f47c7d098be36b931883fb4850090e920ca4ee698b548d8803bc",
    "gaps.smoke.2147495993": "b742235ebaf4df76846eedbaa1f9f7615d11236875a7feef46ab71fd2924ffbe",
    "work.smoke": "6b5b8289fba5df3d41c11d558d013f8c6da83cec245f1ee76bb82baaa6e4626e",
    "work.full": "17232c034df7f0fb9ce3fa15ec9cb789ad75d1ddc9a8a8f9fc0619d57ab1f1df",
    "arch.full": "92a0a77723f8fb14d3dab8a8c487f9c3d809a24a1b25168ab39eb0d9b40bdde1",
    "shapes.full": "3d980449054c44e6bd7e926941d95799dbe97eb0d03af341666418c01bb64160",
}


@pytest.fixture(scope="module")
def digests():
    p = subprocess.run([sys.executable, str(HERE / "tests" / "digests.py")], env=ENV,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout)


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_llama_draws_computes_and_counts_what_it_did_before_the_move(key, digests):
    assert digests[key] == GOLDEN[key]


def test_the_llama_module_defines_the_interface():
    import run

    m = run.load_arch("llama")
    for name in run.ARCH_NAMES + ("hidden", "logits"):
        assert callable(getattr(m, name, None)), name


def test_llama_controls_follow_what_the_file_reduces():
    import run

    m = run.load_arch("llama")
    arch = {"rms_norm_eps": 1e-6}
    cut = {"rms_norm_eps": {"published": 1e-5, "run": 1e-6}}
    assert m.controls(arch, {"reduced": cut}) == {"published_eps": {"rms_norm_eps": 1e-5}}
    assert m.controls(arch, {"reduced": {}}) == {}


def test_a_model_type_that_names_no_file_is_refused():
    import run

    for bad in ("../run", "a/b", "", None):
        with pytest.raises(run.Fail, match="names no archs/ module"):
            run.load_arch(bad)
    with pytest.raises(run.Fail, match=r"no archs/nonesuch\.py"):
        run.load_arch("nonesuch")


# -- a second configuration, in a copy of the tree ---------------------------------------------


def _tree(tmp_path: Path, model_type: str, edit=None) -> Path:
    """A copy of the benchmark with a second configuration and cell
    (``second``, ``second-chat``): the smollm file under ``model_type``, the
    same mix, every per-layer metric read in it too."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    first = bench["configs"][0]
    conf = json.loads((ROOT / first["file"]).read_text())
    conf.update(name="second", model_type=model_type)
    if edit:
        edit(conf)
    (chip / "configs" / "second.json").write_text(json.dumps(conf))
    bench["configs"].append({**first, "name": "second", "file": "benchmarks/chip/configs/second.json"})
    bench["workloads"].append({**bench["workloads"][0], "name": "second-chat", "config": "second"})
    for m in bench["per_layer"]:
        m["workloads"].append("second-chat")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return chip


def _rehearse(root: Path, *args: str):
    return subprocess.run(
        [sys.executable, str(root / "benchmarks" / "chip" / "run.py"), "--workload", "second-chat",
         "--seed", str(2**31 + 5), "--rehearse", *args],
        cwd=root, env=ENV, capture_output=True, text=True, timeout=900)


def test_a_configuration_whose_architecture_has_no_module_fails_naming_the_file(tmp_path):
    _tree(tmp_path, "nomodule")
    p = _rehearse(tmp_path, "--seconds", "2", "--trace", "0")
    assert p.returncode == 1, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert p.stderr.strip().splitlines()[-1] == (
        "[bench] FAIL no archs/nomodule.py for model_type 'nomodule'")


def test_an_unforeseen_error_is_named_in_the_last_line(tmp_path):
    _tree(tmp_path, "llama", edit=lambda conf: conf.pop("weights"))
    p = _rehearse(tmp_path, "--seconds", "2", "--trace", "0")
    assert p.returncode == 1, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert p.stderr.strip().splitlines()[-1] == "[bench] FAIL KeyError: 'weights'"


def test_an_architecture_brought_as_new_files_alone_rehearses(tmp_path):
    chip = _tree(tmp_path, "llama_copy")
    shutil.copy(chip / "archs" / "llama.py", chip / "archs" / "llama_copy.py")
    p = _rehearse(tmp_path, "--seconds", "3", "--trace", "1")
    assert p.returncode == 3, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert "correct=True" in p.stderr
