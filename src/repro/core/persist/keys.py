"""Cache-key discipline for the artifact store (DESIGN.md §14).

Nothing derived from Python's process-salted ``hash()`` (entry stamps,
``avals_digest``, ``FoldedConst._key``) ever reaches disk: on-disk keys
are sha256 digests of the canonical JSON form produced by codec.py.  The
store namespace folds in the jax version, the active backend and device
kind, and a digest of the repro source tree, so upgrading jax, switching
platform or chip generation, or editing the engine makes every prior
artifact a clean miss — never a wrong load.
``$TERRA_CACHE_SALT`` is appended to the namespace when set (the tests'
version-skew lever; also handy for manual cache busting)."""

from __future__ import annotations

import functools
import hashlib
import json
import os
import re
import subprocess
from typing import Optional

import jax

from repro.core.persist import codec


def canonical_json(v) -> str:
    """Deterministic JSON for any codec-encodable value; raises
    :class:`codec.CodecError` otherwise."""
    return json.dumps(codec.encode(v), sort_keys=True,
                      separators=(",", ":"))


def digest_of(v) -> Optional[str]:
    """sha256 digest of a value's canonical form, or None when the value
    is not encodable (callers treat None as 'not persistable')."""
    try:
        s = canonical_json(v)
    except codec.CodecError:
        return None
    return hashlib.sha256(s.encode("utf-8")).hexdigest()[:24]


def _git_listed(root: str) -> Optional[set]:
    """Absolute paths under ``root`` that git tracks or would track (an
    untracked file no ignore rule matches), or None when ``root`` is not
    inside a git work tree (or git is unavailable)."""
    try:
        out = subprocess.run(["git", "ls-files", "-z", "--cached", "--others",
                              "--exclude-standard", "--", "."], cwd=root,
                             capture_output=True, check=True,
                             timeout=30).stdout
    except (OSError, subprocess.SubprocessError):
        return None
    return {os.path.join(root, p) for p in out.decode().split("\0") if p}


def source_files(root: str, anchor: str) -> list:
    """The .py files under ``root`` that make up the code, sorted.  Files
    git ignores do not count, so a build output or a scratch copy of the
    tree does not move the key.  When git's listing lacks ``anchor`` (no
    git, or ``root`` is itself a gitignored copy, where git lists
    nothing) every .py file counts."""
    listed = _git_listed(root)
    if listed is not None and anchor not in listed:
        listed = None
    paths = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for fn in filenames:
            p = os.path.join(dirpath, fn)
            if fn.endswith(".py") and (listed is None or p in listed):
                paths.append(p)
    return sorted(paths)


@functools.lru_cache(maxsize=1)
def code_digest() -> str:
    """Digest of the repro package's .py files (:func:`source_files`) —
    any source edit invalidates the whole cache namespace."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    h = hashlib.sha256()
    for p in source_files(root, os.path.abspath(__file__)):
        h.update(os.path.relpath(p, root).encode("utf-8"))
        try:
            with open(p, "rb") as f:
                h.update(f.read())
        except OSError:
            h.update(b"<unreadable>")
    return h.hexdigest()[:16]


def namespace() -> str:
    """Versioned manifest key: the store root subdirectory all artifacts
    of this (jax version, backend, device kind, code) combination live
    under."""
    kind = re.sub(r"[^A-Za-z0-9]+", "_", jax.devices()[0].device_kind)
    ns = (f"jax{jax.__version__}-{jax.default_backend()}-{kind}"
          f"-code{code_digest()}")
    salt = os.environ.get("TERRA_CACHE_SALT", "")
    if salt:
        ns += f"-{salt}"
    return ns


def family_dir(scope: str, feed_sig) -> Optional[str]:
    """Relative directory holding all candidate records for one
    (function scope, feed signature) pair; sibling var-aval classes are
    sibling files inside it."""
    d = digest_of(("family", scope, feed_sig))
    return None if d is None else f"fam/{d}"


def record_name(var_avals: dict) -> Optional[str]:
    d = digest_of(("vars", tuple(sorted(var_avals.items()))))
    return None if d is None else f"{d}.json"


def segment_rel(signature, var_avals_of_reads) -> Optional[str]:
    """Relative path of a segment's AOT executable.  The structural
    signature does not capture variable avals (var_reads are raw ids), so
    they are folded in here — two families sharing a signature but
    differing in buffer shapes must not share an executable."""
    d = digest_of(("segment", signature, var_avals_of_reads))
    return None if d is None else f"seg/{d}.bin"
