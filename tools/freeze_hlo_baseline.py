"""Freeze the lowering digests of tests/_hlo_matrix.py to
tests/data/hlo_pr6.json.

    env -u XLA_FLAGS PYTHONPATH=src JAX_PLATFORMS=cpu \
        python tools/freeze_hlo_baseline.py

Run from a tree whose programs are the reference (an engine whose
lowering is known-good); tests/test_env.py::test_env_off_lowering_frozen
then pins every later tree's lowering against it byte for byte (same jax
version and backend only — the digests are compiler-version specific).
"""
from __future__ import annotations

import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tests"))

from _hlo_matrix import environment_tag, lowering_digests  # noqa: E402


def main() -> None:
    payload = {**environment_tag(), "digests": lowering_digests()}
    out = REPO / "tests" / "data" / "hlo_pr6.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"[freeze_hlo_baseline] wrote {len(payload['digests'])} digests "
          f"to {out} (jax {payload['jax_version']}, "
          f"backend {payload['backend']})")


if __name__ == "__main__":
    main()
