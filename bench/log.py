"""Progress notes on standard error, stamped with the host clock."""

import sys
import time


def note(msg: str) -> None:
    print(f"bench: [{time.perf_counter():.3f}] {msg}", file=sys.stderr,
          flush=True)
