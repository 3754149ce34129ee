"""Candidates the device program drew per opened center, mean over the
traced window's seedings (`FitResult.extras["trials"]`, Lemma 5.3)."""

import numpy as np


def read(run):
    trials = [np.asarray(a.trials, np.float64).reshape(-1)
              for a in run.window["answers"] if a.trials is not None]
    if not trials:
        return None
    return float(np.concatenate(trials).mean())
