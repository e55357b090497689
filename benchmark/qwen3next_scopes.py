"""What the `qwen3next_lm` family's per-layer metrics read beside
`xing4_scopes.py`'s scopes: the device time under a Gated DeltaNet's
`gdn/.../scan` (`veles_tpu/ops/linear_attention.py`; the chain along the
sequence is the body of a `lax.scan`, so its path runs `.../scan/while/
body/...`, and its backward opens `gdn/scan` again)."""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

from benchmark import xing4_scopes as X

#: `scan` as a whole component somewhere beneath `gdn`, with something
#: beneath it: where a layer walks its sequences in groups
#: (`scan_groups`) the path runs `gdn/while/body/closed_call/checkpoint/
#: scan/...`, and the group loop's own `scan` equation, at `gdn/scan` with
#: nothing beneath, is no part of it
SCAN = re.compile(r"(?<![A-Za-z0-9_.])gdn(?:/[^/\"]+)*?/scan/")


def scan_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """Seconds a step of the traced run spent under `gdn/scan`; None where
    there is nothing to read (a program without such a layer, a run that
    was not traced)."""
    return X.scope_seconds(ctx, SCAN)
