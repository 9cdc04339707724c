"""host pump: self time of ``pump.deliver`` — the transport handing a
round's messages to ``on_message`` and the admission checks there — per
round."""

from benchmarks.harness import spanbook


def read(obs):
    return spanbook.self_ms_per_round(obs, "pump.deliver")
