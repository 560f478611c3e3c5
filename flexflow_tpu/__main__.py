"""``python -m flexflow_tpu user_script.py [flags]`` — the TPU analog of
the reference's ``flexflow_python`` custom interpreter
(``python/flexflow_python_build.py`` + ``flexflow_top.py:164-221``): run a
user script with the FlexFlow flags available on ``sys.argv``.

No Legion top-level task exists here: the launcher just forwards argv (the
script builds ``FFConfig`` and calls ``parse_args`` itself, like the
reference's scripts) and runs the file as ``__main__``.  Multi-host
bootstrap happens inside ``FFModel`` construction as usual.
"""

from __future__ import annotations

import runpy
import sys


def main() -> int:
    if len(sys.argv) >= 2 and sys.argv[1] == "--serve":
        # serving demo driver (docs/SERVING.md): continuous batching +
        # paged KV cache over a gpt_decoder, fed by a synthetic
        # open-loop traffic generator — no user script involved
        from flexflow_tpu.serve.driver import main as serve_main

        return serve_main(sys.argv[2:])
    if len(sys.argv) < 2 or sys.argv[1] in ("-h", "--help"):
        print(
            "usage: python -m flexflow_tpu <script.py> [flexflow flags...]\n"
            "       python -m flexflow_tpu --serve [serve flags...]\n"
            "Runs <script.py> as __main__ with the remaining args on "
            "sys.argv (FFConfig.parse_args consumes FlexFlow flags); "
            "--serve runs the continuous-batching serving driver "
            "(docs/SERVING.md).",
            file=sys.stderr,
        )
        return 0 if len(sys.argv) >= 2 else 2
    script = sys.argv[1]
    sys.argv = sys.argv[1:]
    # before the user script runs, so EVERY compile of this process — not
    # just those after FFModel construction — is cacheable
    from flexflow_tpu.config import apply_compile_cache

    apply_compile_cache()
    runpy.run_path(script, run_name="__main__")
    return 0


if __name__ == "__main__":
    sys.exit(main())
