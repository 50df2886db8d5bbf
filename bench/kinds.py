"""The model kind of a configuration, found by the name in its ``reference``
key: the module ``bench/reference/<reference>.py``.

A kind holds everything the harness knows of one family of models, so that
a new family comes in as a new file and no generic file names its keys:

  param_shapes(cfg)           {name: (shape, fan_in or None for a norm
                              scale)}, nested like the trainer's parameter
                              tree; the seeded weights, the layout check,
                              the comparison and the kernel bytes read it
  matmul_weights(cfg)         weights one token multiplies (of a layer with
                              experts, the active ones only)
  flops_per_token(cfg, seq)   model FLOPs of one token, forward and backward
  program_params(cfg)         the program's ``ModelSpec.params``; ValueError
                              where the program cannot run the configuration
  loss_and_grad(cfg, params, tokens, labels)
                              the plain reference's mean loss and gradient

A kind imports nothing of the program under test."""
from __future__ import annotations

import importlib
import json
import re

API = ("param_shapes", "matmul_weights", "flops_per_token", "program_params",
       "loss_and_grad")
_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


class BenchError(RuntimeError):
    """A run that cannot measure: no result is printed."""


def of(cfg: dict):
    """The kind module of ``cfg``, or BenchError naming what is missing."""
    name = cfg.get("reference")
    if not isinstance(name, str) or not _NAME.match(name):
        raise BenchError(f"configuration {cfg.get('name')!r} names no model "
                         f"kind: reference {name!r}")
    module = "bench.reference." + name
    try:
        mod = importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise BenchError(f"unknown model kind {name!r} (the reference of "
                         f"configuration {cfg.get('name')!r}): no "
                         f"bench/reference/{name}.py") from None
    missing = [f for f in API if not hasattr(mod, f)]
    if missing:
        raise BenchError(f"bench/reference/{name}.py is not a model kind: "
                         f"it has no {', '.join(missing)}")
    return mod


def frozen(cfg: dict) -> str:
    """The whole configuration, nested entries too, as a hashable key;
    ``json.loads`` gives it back."""
    return json.dumps(cfg, sort_keys=True)
