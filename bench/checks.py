"""The numbers a run compares with its plain reference, each with its
limit; a run is correct when none exceeds its limit."""
from __future__ import annotations

from typing import Dict, List


def check(name: str, value: float, limit: float) -> dict:
    return {"name": name, "value": value, "limit": limit,
            "ok": value <= limit}


def correct(checks: List[dict]) -> bool:
    return bool(checks) and all(c["ok"] for c in checks)


def as_result(checks: List[dict]) -> Dict[str, dict]:
    return {c["name"]: {"value": c["value"], "limit": c["limit"]}
            for c in checks}
