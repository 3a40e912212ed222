"""select: the share of select's device time spent joining the kernel's
front-packed survivor tiles into each row's buffer (``_concat_tiles``,
scope ``select/compact``), in percent.  An op belongs to a stage when
its JAX name stack (``tf_op``) holds the stage's ``jax.named_scope``; on
a TPU that name sits in the op's event metadata, which ``devtrace``'s
reader does not see, so this reads the run's own ``.xplane.pb``
(``xplane.py``).  Ops nest (a loop and its body both appear), so each
stage's time is the union of its ops' intervals inside the window.  None
where the program carries no ``select`` scope or the profile is gone."""
import re
from pathlib import Path

import xplane

ROOT = Path(__file__).resolve().parents[2]
SELECT = re.compile(r"(^|/)select/")
COMPACT = re.compile(r"(^|/)select/(jit\([^/]*\)/)*compact/")


def share(devices, win):
    select = xplane.scope_seconds(devices, win, SELECT)
    if select <= 0:
        return None
    return 100.0 * xplane.scope_seconds(devices, win, COMPACT) / select


def read(ctx):
    if ctx.trace is None:
        return None
    found = xplane.this_runs_profile(ctx.trace, str(ROOT))
    return None if found is None else share(*found)
