"""Share of the window's commands whose record holds more than ``above`` in
``column`` (a generator's own column, such as the shards a command touched);
``scale`` multiplies it (100 for %).  A history without the column, or a
window without commands, reads nothing."""

import numpy as np


def read(ctx, column, above, scale=1.0):
    values = ctx["measured"].get(column)
    if values is None or not len(values):
        return None
    return float(scale * np.mean(values > above))
