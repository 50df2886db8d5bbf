"""A model kind is found by the name in a configuration's ``reference`` key
(bench/kinds.py): a new kind runs through the harness as a new module, an
unknown one is refused, and no generic file names a dense decoder's keys."""
import glob
import json
import os
import shutil
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

import tiny
from _bench_path import ROOT
from bench import counts, kinds, run, weights
from bench.reference import dense_decoder, prox_lead

# keys every kind's configuration file has, which generic files may read
GENERIC_KEYS = {"name", "source", "paper", "architectures", "vocab_size",
                "torch_dtype", "padded_vocab_multiple", "published",
                "reduced", "assumed", "program_arch", "reference",
                "deployment"}


def _dense_configs():
    paths = glob.glob(os.path.join(ROOT, "bench", "configs", "*.json"))
    paths.append(os.path.join(tiny.DATA, "tiny-2L.json"))
    out = []
    for p in paths:
        with open(p) as f:
            cfg = json.load(f)
        if cfg["reference"] == "dense_decoder":
            out.append(cfg)
    return out


def _tiny_cfg(**changes):
    c = tiny.cell("node1.q2")
    c["cfg"] = dict(c["cfg"], **changes)
    return c


def _renamed(tree, old, new):
    out = dict(tree)
    out[new] = out.pop(old)
    return out


def _kind(name, monkeypatch, seen):
    """A kind ``bench.reference.<name>`` that wraps dense_decoder and
    records the configuration each of its functions was handed.  Its
    reference holds the final norm under a name of its own, ``norm_out``,
    and converts at its boundary: the harness hands it, and takes back,
    the parameters in the program's layout."""
    mod = types.ModuleType("bench.reference." + name)

    def record(fn, f):
        def wrapped(cfg, *args):
            seen.setdefault(fn, []).append(cfg)
            return f(cfg, *args)
        return wrapped

    def loss_and_grad(cfg, params, tokens, labels):
        own = _renamed(params, "final_norm", "norm_out")
        return dense_decoder.loss_and_grad(
            cfg, _renamed(own, "norm_out", "final_norm"), tokens, labels)

    for fn in kinds.API:
        f = loss_and_grad if fn == "loss_and_grad" else getattr(
            dense_decoder, fn)
        setattr(mod, fn, record(fn, f))
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def test_a_new_kind_runs_as_a_new_module(monkeypatch, tmp_path):
    seen = {}
    _kind("renamed_dense", monkeypatch, seen)
    c = _tiny_cfg(reference="renamed_dense")
    res = tiny.run(c, 3_000_000_019, str(tmp_path))
    assert res["correct"], res["compared"]
    assert {"param_shapes", "program_params", "loss_and_grad"} <= set(seen)
    cfg = c["cfg"]
    assert counts.flops_per_token(cfg, 32) == dense_decoder.flops_per_token(
        cfg, 32)
    assert counts.matmul_weights(cfg) == dense_decoder.matmul_weights(cfg)
    assert set(seen) == set(kinds.API)
    assert all(s["reference"] == "renamed_dense"
               for calls in seen.values() for s in calls)


@pytest.mark.parametrize("fn", ["grad", "change", "loss_and_grad"])
def test_a_nested_entry_reaches_the_kind(fn, monkeypatch):
    seen = {}
    _kind("nested_probe", monkeypatch, seen)
    yarn = {"type": "yarn", "factor": 40, "beta_fast": 32}
    cfg = _tiny_cfg(reference="nested_probe", rope_scaling=yarn,
                    published={"vocab_size": 4000})["cfg"]
    key = kinds.frozen(cfg)
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, weights.dtype_of(cfg)),
        weights.leaf_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(d, int) for d in x))
    wkey = jax.eval_shape(lambda: weights.key(5))
    tok = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    if fn == "grad":
        jax.eval_shape(run._ref_grad_fn(key, 0.05), wkey, shapes, shapes)
        got = seen["param_shapes"]
    elif fn == "change":
        jax.eval_shape(run._ref_change_fn(key), wkey, shapes, shapes, shapes,
                       shapes)
        got = seen["param_shapes"]
    else:
        jax.eval_shape(prox_lead._loss_grad_fn(key), shapes, tok, tok)
        got = seen["loss_and_grad"]
    assert got and all(g["rope_scaling"] == yarn for g in got)
    assert all(g["published"] == {"vocab_size": 4000} for g in got)


@pytest.mark.parametrize("reference,msg", [
    ("no_such_kind", "unknown model kind 'no_such_kind'"),
    ("prox_lead", "is not a model kind"),
    ("../configs", "names no model kind"),
    (None, "names no model kind"),
])
@pytest.mark.parametrize("call", ["param_shapes", "matmul_weights",
                                  "flops_per_token", "model_params"])
def test_an_unknown_kind_is_refused(reference, msg, call):
    cfg = dict(tiny.cell("node1.q2")["cfg"], reference=reference)
    f = {"param_shapes": weights.param_shapes,
         "matmul_weights": counts.matmul_weights,
         "flops_per_token": lambda c: counts.flops_per_token(c, 32),
         "model_params": run.model_params}[call]
    with pytest.raises(run.BenchError, match=msg):
        f(cfg)


def test_an_unknown_kind_exits_2_without_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(tmp_path / "BENCHMARK.json") as f:
        manifest = json.load(f)
    cell = manifest["workloads"][0]
    cfg_file = {c["name"]: c["file"] for c in manifest["configs"]}[
        cell["config"]]
    with open(tmp_path / cfg_file) as f:
        cfg = json.load(f)
    cfg["reference"] = "no_such_kind"
    with open(tmp_path / cfg_file, "w") as f:
        json.dump(cfg, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        cell["name"], "--seed", "2147483659", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "no_such_kind" in p.stderr
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("changes,msg", [
    ({"hidden_act": "gelu"}, "SwiGLU with an untied lm_head"),
    ({"tie_word_embeddings": True}, "SwiGLU with an untied lm_head"),
    ({"rms_norm_eps": 1e-5}, "RMSNorm eps is 1e-6"),
    ({"padded_vocab_multiple": 128}, "multiple of 256"),
])
def test_dense_program_params_refusals(changes, msg):
    cfg = dict(tiny.cell("node1.q2")["cfg"], **changes)
    with pytest.raises(run.BenchError, match=msg):
        run.model_params(cfg)


def _kind_modules():
    out = set()
    for p in glob.glob(os.path.join(ROOT, "bench", "reference", "*.py")):
        with open(p) as f:
            if "\ndef param_shapes(" in f.read():
                out.add(os.path.abspath(p))
    return out


def test_no_generic_file_names_a_dense_key():
    dense_keys = set()
    for cfg in _dense_configs():
        dense_keys |= set(cfg) - GENERIC_KEYS
    assert {"intermediate_size", "num_key_value_heads", "head_dim",
            "qk_norm"} <= dense_keys
    kind_files = _kind_modules()
    assert os.path.join(ROOT, "bench", "reference",
                        "dense_decoder.py") in kind_files
    generic = [p for p in glob.glob(os.path.join(ROOT, "bench", "**", "*.py"),
                                    recursive=True)
               if os.path.abspath(p) not in kind_files]
    for name in ("run.py", "weights.py", "counts.py",
                 os.path.join("reference", "prox_lead.py")):
        assert os.path.join(ROOT, "bench", name) in generic
    found = []
    for p in generic:
        with open(p) as f:
            src = f.read()
        found += [(os.path.relpath(p, ROOT), k) for k in sorted(dense_keys)
                  if f'"{k}"' in src or f"'{k}'" in src]
    assert found == []
