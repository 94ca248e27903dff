"""Shared deprecation helpers.

One home for the ``fused=`` deprecation shims so every back-compat
entry point resolves and warns the same way.
"""
from __future__ import annotations

import warnings

from ..sparse.dispatch import method_from_fused


def resolve_method_arg(fused: bool | None, method: str | None,
                       *, api: str, stacklevel: int = 3) -> str:
    """Map the deprecated ``fused=`` flag to a ``method`` string, warning.

    Shared by every back-compat entry point so the deprecation message
    and resolution semantics cannot drift apart.  The warning names the
    *exact* replacement call for the flag value that was passed, so the
    migration is a copy-paste.
    """
    if fused is not None:
        resolved = method_from_fused(fused, method)
        warnings.warn(
            f"{api}(..., fused={bool(fused)}) is deprecated; call "
            f"{api}(..., method='{resolved}') instead — see "
            "repro.sparse for the full backend table",
            DeprecationWarning,
            stacklevel=stacklevel,
        )
    return method_from_fused(fused, method)
