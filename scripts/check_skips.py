#!/usr/bin/env python
"""Silent-skip audit for the smoke run.

A skipped test that nobody registered is coverage rotting quietly: the
suite stays green while an entire subsystem stops executing (the failure
mode this repo hit when ``hypothesis``-gated property tests skipped
whole-module for years of CI time).  This script parses the junit XML the
smoke pytest run emits and fails unless EVERY skip carries a reason
matching the registry below — adding a new legitimate skip means adding
its reason here, in review, on purpose.

Usage:  python scripts/check_skips.py JUNIT_XML_PATH
"""
from __future__ import annotations

import re
import sys
import xml.etree.ElementTree as ET

# Every legitimate skip reason in this repo, as a regex.  A skip whose
# message matches none of these fails the smoke.
REGISTERED_REASONS = [
    r"hypothesis not installed in this container",
    r"no v5e:2x2 topology can be described here",
]


def audit(path: str) -> int:
    """Return the number of UNREGISTERED skips in the junit file (printing
    each), after listing the registered ones."""
    root = ET.parse(path).getroot()
    bad = 0
    for case in root.iter("testcase"):
        skipped = case.find("skipped")
        if skipped is None:
            continue
        name = f"{case.get('classname')}::{case.get('name')}"
        reason = (skipped.get("message") or skipped.text or "").strip()
        if reason and any(re.search(p, reason) for p in REGISTERED_REASONS):
            print(f"[check_skips] ok   {name}: {reason}")
        else:
            bad += 1
            print(f"[check_skips] FAIL {name}: unregistered skip "
                  f"reason {reason!r}")
    return bad


def main() -> None:
    """CLI entry: exit non-zero when any silent/unregistered skip exists."""
    if len(sys.argv) != 2:
        raise SystemExit("usage: check_skips.py JUNIT_XML_PATH")
    bad = audit(sys.argv[1])
    if bad:
        raise SystemExit(
            f"[check_skips] {bad} test(s) skipped without a registered "
            f"reason — register the reason in scripts/check_skips.py or "
            f"fix the skip")
    print("[check_skips] no silent skips")


if __name__ == "__main__":
    main()
