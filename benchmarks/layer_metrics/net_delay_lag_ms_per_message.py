"""transport: how much longer than its link's delay a held message of
validator 0 waited, on average — ``net.delay`` books the real wait of
each; the configured mean comes from the matrix (validator 0's region
to every peer's, the jitter being symmetric). The delay thread's
lateness: what a loaded host adds to the WAN it plays."""

from benchmarks.harness import validatorbook
from benchmarks.harness.spanbook import ratio


def read(obs):
    book = validatorbook.open_book(obs)
    config = obs.get("config", {})
    if book is None or "one_way_delay_ms" not in config:
        return None
    waited = ratio(book.total_ns("net.delay"), book.count("net.delay"), 1e-6)
    if waited is None:
        return None
    names, n = config["regions"], config["n"]
    here = config["one_way_delay_ms"][names[0]]
    asked = sum(here[names[i % len(names)]] for i in range(1, n)) / (n - 1)
    return waited - asked
