"""host pump: admission into the DAG — self times of ``pump.inbox``,
``pump.cert``, ``pump.insert``, ``pump.collect``, ``pump.apply`` and the
catch-up ``pump.sync`` — per round."""

from benchmarks.harness import spanbook


def read(obs):
    return spanbook.self_ms_per_round(
        obs, "pump.inbox", "pump.cert", "pump.insert", "pump.collect",
        "pump.apply", "pump.sync",
    )
