"""Does a change leave a configuration's compiled train step alone? One
hash of the step `benchmark/tools/compile_step*.py` compiles for a
described v5e, with debug locations set aside: the tool is run with
`JAX_TRACEBACK_IN_LOCATIONS_LIMIT=0`, under which no location holds a
file or a line (the Mosaic kernel bodies' neither, whose call stacks are
otherwise in the persistent cache's key: ROADMAP S13), and the
compiled text is hashed without its `metadata={...}`. Equal hashes on
two checkouts say the two compile to the same program; nothing runs and
no chip is needed.

    python tools/compiled_step_hash.py <checkout> <tool> <config.json> [batch]

e.g. `python tools/compiled_step_hash.py _checkout/parent
compile_step_afmoe benchmark/configs/trinity-mini-p1-e16v8.json`, then
the same with `.` for the checkout. Prints `<sha256> <characters>`.
"""

from __future__ import annotations

import hashlib
import os
import re
import subprocess
import sys
import tempfile

_CHILD = """
import os, runpy, sys
import jax
compile_ = jax.stages.Lowered.compile
def keep(self, *a, **k):
    compiled = compile_(self, *a, **k)
    with open(os.environ["EDL_KEEP_COMPILED"], "w") as f:
        f.write(compiled.as_text())
    return compiled
jax.stages.Lowered.compile = keep
sys.argv = sys.argv[1:]
runpy.run_path(sys.argv[0], run_name="__main__")
"""


def main(argv: list[str]) -> int:
    checkout, tool, *rest = argv
    checkout = os.path.abspath(checkout)
    with tempfile.TemporaryDirectory() as tmp:
        kept = os.path.join(tmp, "step.hlo")
        out = subprocess.run(
            [sys.executable, "-c", _CHILD,
             os.path.join("benchmark", "tools", tool + ".py"), *rest],
            cwd=checkout, capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "JAX_TRACEBACK_IN_LOCATIONS_LIMIT": "0",
                 "PYTHONPATH": checkout, "EDL_KEEP_COMPILED": kept})
        if out.returncode != 0 or not os.path.exists(kept):
            print(out.stderr[-3000:], file=sys.stderr)
            return 1
        with open(kept) as f:
            text = re.sub(r",? ?metadata=\{[^}]*\}", "", f.read())
    print(hashlib.sha256(text.encode()).hexdigest(), len(text))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
