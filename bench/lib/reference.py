"""The plain reference a configuration names.

A configuration file's ``"reference"`` key names a module
``<bench>/reference/<name>.py``; there is no default.  The harness
reaches the reference only through it, so a configuration whose
architecture needs its own layout and mathematics brings its own
module and edits nothing.  The module provides:

* ``make_weights(seed, m, *, dtype=None, program=True)``: the seed's
  weights, made on the device in one call, in the program's layout or
  the reference's canonical one (a dict whose ``"layers"`` leaves are
  stacked on a leading layer axis, as ``check.leaf_norms`` reads them);
* ``from_program(tree, m)``: a program-layout tree (parameters, or an
  optimizer moment of the same structure) in the canonical layout;
* ``loss_and_grad(params, batch, m, *, keep_rows=None,
  precision="fp32")``: the token-weighted mean loss of a batch and its
  gradient, computed in blocks that fit the device;
* ``logits(params, tokens, lengths, m, precision="fp32")``;
* ``AdamW``: the optimizer of the training reference.

A configuration's ``"control"`` says what its control run changes:
``{"on": "program", "dtype": ...}`` runs the program's own path in that
dtype (training only), ``{"on": "reference", "precision": ...}`` puts
the reference, computed at that precision, in the program's place (a
serving cell reads the gap of the token that precision puts first).
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]


@functools.lru_cache(maxsize=None)
def _load(path: pathlib.Path):
    name = "bench_reference_" + path.stem
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(cfg: dict, bench_dir: pathlib.Path = BENCH):
    """The reference module ``cfg["reference"]`` names."""
    path = (pathlib.Path(bench_dir) / "reference"
            / f"{cfg['reference']}.py").resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no reference module {path}")
    return _load(path)


def control(cfg: dict) -> dict:
    """The configuration's control, checked."""
    c = cfg["control"]
    if c.get("on") == "program" and "dtype" in c \
            and cfg.get("kind") == "train":
        return c
    if c.get("on") == "reference" and "precision" in c:
        return c
    raise ValueError(f"control {c!r}: want {{'on': 'reference', "
                     "'precision': ..}, or for training {'on': 'program', "
                     "'dtype': ..}")
