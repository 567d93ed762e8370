"""One module per Tsetlin-machine kind: ``kinds/<kind>.py``.

A configuration file names its kind in ``"kind"`` (an ``api.TMSpec``
kind name); :func:`of` finds the module by that name, and the harness,
the control, the metric readers and the work counts call it for every
piece that depends on the kind.  A configuration of a new kind therefore
adds ``kinds/<kind>.py`` (with its plain reference beside it) and
touches no file that is already here.  ``kinds/coalesced.py`` states the
contract a kind module keeps.
"""
from __future__ import annotations

import importlib
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def of(cfg: dict):
    """The kind module of configuration ``cfg``.  A configuration that
    names no kind, or a kind with no module, is an error that names the
    file looked for; there is no default kind."""
    kind = cfg.get("kind")
    if not isinstance(kind, str) or not kind.isidentifier():
        raise LookupError(
            f"configuration {cfg.get('name')!r} names no \"kind\" (got "
            f"{kind!r}); its module would be {HERE / '<kind>.py'}")
    path = HERE / f"{kind}.py"
    if not path.is_file():
        raise LookupError(f"configuration {cfg.get('name')!r}: kind "
                          f"{kind!r} has no module {path}")
    return importlib.import_module(f"{__name__}.{kind}")
