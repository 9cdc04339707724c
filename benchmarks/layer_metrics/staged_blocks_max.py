"""mempool: the deepest ``Process.blocks_to_propose`` seen at a cycle's start."""


def read(obs):
    return obs["counters"].get("staged_blocks_max")
