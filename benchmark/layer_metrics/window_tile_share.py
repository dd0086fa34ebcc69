"""The score tiles a window layer's forward kernel runs over those of a
full causal layer, the mean over the `gqa` layers with a window: the
program's counters `<layer>.tiles` (layers/lm.py; static a layer, 1
where nothing is skipped), which the driver fetches after the window.
Under 1 the window is skipped, not masked. Silent where the window
carries no such counter."""

from benchmark import scope_keys


def read(obs):
    counters = getattr(obs.window, "counters", None) or {}
    got = [counters[l.name + ".tiles"]
           for l in scope_keys.conf_layers(obs, "gqa", scope_keys.has_window)
           if l.name + ".tiles" in counters]
    return sum(got) / len(got) if got else None
