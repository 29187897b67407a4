"""The tiled generation kernel's tile, pinned by the configuration.

Left to itself the program picks the tile by a timing race on the first
run of a checkout (``kernels/ga/autotune.py``), so two checkouts of the
same code can run different tilings and differ by more than a bound. A
configuration with a ``tiles`` entry fixes it: before anything is traced
the run writes that tile into a cache file of its own, at a fixed path in
the checkout, under the key the program looks up, and points the program
at it (``REPRO_GA_AUTOTUNE_CACHE``). After the warm-up :func:`check` makes
sure the program took the pin and ran no sweep of its own.
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Any, Dict

CACHE = Path(__file__).resolve().parents[1] / ".state" / "autotune_ga.json"


def entries(cfg: Dict[str, Any], device_kind: str) -> Dict[str, dict]:
    """The cache entries that pin the configuration's tile, keyed as the
    program keys them: device kind | genome kind | rows x genes."""
    t = cfg.get("tiles")
    if not t:
        return {}
    g = cfg["genome"]
    n, L = int(cfg["population"]), int(g["length"])
    return {f"{device_kind}|{g['kind']}|{n}x{L}": {
        "shape": [n, L], "kind": g["kind"], "tile_pop": int(t["tile_pop"]),
        "tile_len": int(t["tile_len"]), "timed": False}}


def pin(cfg: Dict[str, Any], device_kind: str, path: Path = CACHE) -> None:
    """Write the pinned entries (none for a configuration without
    ``tiles``) and point the program's tile cache at them."""
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(entries(cfg, device_kind), indent=1,
                               sort_keys=True) + "\n")
    os.environ["REPRO_GA_AUTOTUNE_CACHE"] = str(path)


def check(cfg: Dict[str, Any], device_kind: str, path: Path = CACHE) -> None:
    """Raise when the program's cache holds anything but the pin: the
    program then swept for a shape of its own and picked by the race."""
    want = entries(cfg, device_kind)
    have = json.loads(path.read_text()) if path.is_file() else {}
    if set(have) != set(want):
        raise RuntimeError(
            f"tile pin not taken: the program's tile cache holds "
            f"{sorted(have)}, the configuration pins {sorted(want)}")
