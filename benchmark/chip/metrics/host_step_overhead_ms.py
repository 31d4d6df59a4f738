"""The host's share of a training step: the traced window over its
steps, less the device's busy time a step. Layer: executor
(Executor.run: feed, dispatch, fetch); moves train_tokens_per_s."""


def read(obs):
    tr, steps = obs["trace"], obs["counters"].get("traced_steps")
    if not tr or not steps or not tr["busy_s"]:
        return None
    return (tr["window_s"] - tr["busy_s"]) / steps * 1e3
