"""A configuration's architecture: the one module that knows its layer layout.

A configuration file names its architecture as its published ``config.json``
does (``"architectures": ["Qwen2ForCausalLM"]``). ``resolve`` imports
``bench.arch.<family>``, the family being that name without its ``For...``
head, in lower case (``qwen2``), and checks that the module claims the name
in its ``ARCHITECTURES``. An unknown name is an error. Such a module provides
everything the harness, the output check and the metric readers need that
depends on the architecture:

- ``program_config(cfg)``: the program's ``ModelConfig`` for the file;
- ``dims(cfg)``: the sizes the functions below take, a dict of numbers;
- ``program_params(seed, D)``: the served path's parameter pytree, made on
  the device from the seed;
- ``logits(seed, D, pairs, *, block)``, ``served_gaps(ref_logits, served)``
  and ``rms_deviation(logits, ref_logits)``: the plain reference, from the
  seed alone (the module is the one place that names the reference);
- ``step_flops(D, tokens, ctx_sum, outputs)``,
  ``token_flops(D, ctx_tokens, *, logits)`` and ``decode_bytes(D, ctx_sum)``:
  the operations and least bytes of a served step, from the shapes alone.

A new family is a new file in this directory; no other file changes.
"""
from __future__ import annotations

import importlib
import re

_HEAD = re.compile(r"For[A-Z][A-Za-z]*$")


class UnknownArchitecture(ValueError):
    """No module in ``bench/arch`` serves a configuration's architecture."""


def resolve(cfg: dict):
    """The architecture module of a configuration file's dict."""
    names = cfg.get("architectures")
    if not isinstance(names, list) or len(names) != 1 \
            or not isinstance(names[0], str):
        raise UnknownArchitecture(
            f"configuration {cfg.get('name')!r}: 'architectures' must name "
            f"one architecture, not {names!r}")
    name = names[0]
    fam = _HEAD.sub("", name).lower()
    if not fam.isidentifier():
        raise UnknownArchitecture(f"no module for architecture {name!r}")
    modname = f"{__name__}.{fam}"
    try:
        mod = importlib.import_module(modname)
    except ModuleNotFoundError as e:
        if e.name != modname:
            raise
        raise UnknownArchitecture(
            f"no module for architecture {name!r} (looked for "
            f"bench/arch/{fam}.py)") from None
    if name not in getattr(mod, "ARCHITECTURES", ()):
        raise UnknownArchitecture(
            f"bench/arch/{fam}.py does not serve {name!r}")
    return mod
