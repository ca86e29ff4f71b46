"""campaign_cand_per_s: candidate x workload evaluations of every campaign
completed in the window, over the time from the window's start to the last
completion."""


def read(obs):
    done = obs.get("completions") or []
    if obs.get("kind") != "campaign" or not done:
        return None
    return sum(obs["candidate_evals"]) / (done[-1] - obs["window_start"])
