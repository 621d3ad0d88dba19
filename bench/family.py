"""A configuration's family: the plain reference and the program mapping
that its configuration file names, so that no driver names a family.

The file's ``reference`` key is the path of the reference module,
relative to the root of the checkout or absolute.  The program mapping is
the module of the same stem in the ``programs`` directory beside the
reference's directory: ``bench/reference/dense_decoder.py`` is mapped by
``bench/programs/dense_decoder.py``.  A new family adds those two files.

The reference imports nothing of the program and exports (``REFERENCE``):

- ``Spec.from_config(config)``: the sizes it computes with, from the
  configuration file;
- ``init_weights(spec, key)``: the benchmark's weights from a JAX key, in
  the reference's own tree and the types they are served in; jittable,
  so that set-up makes them on the device in one call;
- ``gaps_and_top(spec, mode, weights, tokens, picks)``: at the last
  positions of each row of ``tokens``, the gap by which the logit of each
  of ``picks`` lies below the best, and the best token; ``mode`` is
  ``"fp32"`` or the control's ``"fp8"``;

and for training cells also (``TRAINING``):

- ``follow_training(spec, hyper, decayed, make_weights, batches, mode)``:
  the reference's losses, first clipped gradient and parameters after
  ``batches``.

The mapping may import the program and exports (``MAPPING``):

- ``program_config(config)``: the program's model configuration with
  every size the file states; it raises for a configuration whose
  reference it does not match;
- ``to_program(weights)``: the reference's weight tree in the program's
  parameter layout, the same arrays;
- ``from_program(params)``: its inverse.
"""

from __future__ import annotations

import importlib
import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
REFERENCE = ("Spec", "init_weights", "gaps_and_top")
TRAINING = ("follow_training",)
MAPPING = ("program_config", "to_program", "from_program")

_loaded: dict = {}


def paths(config: dict) -> tuple[Path, Path]:
    """The reference's and the mapping's files for ``config``."""
    ref = (ROOT / config["reference"]).resolve()
    return ref, ref.parent.parent / "programs" / ref.name


def _module(path: Path):
    """The module at ``path``, loaded once: inside the checkout under its
    package name, so that it is the module the tests import; elsewhere
    from the file, under a name of its own (registered before it runs, as
    a dataclass in it needs)."""
    if path not in _loaded:
        if not path.is_file():
            raise FileNotFoundError(f"no module {path}")
        if path.is_relative_to(ROOT):
            name = ".".join(path.relative_to(ROOT).with_suffix("").parts)
            mod = importlib.import_module(name)
        else:
            name = f"bench_family_{len(_loaded)}_{path.stem}"
            spec = importlib.util.spec_from_file_location(name, path)
            mod = sys.modules[name] = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def load(config: dict):
    """``(reference, mapping)``: the two modules ``config`` names."""
    return tuple(_module(p) for p in paths(config))
