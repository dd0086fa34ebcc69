"""The live score tiles the forward flash kernel masks over those it
runs, the mean over the conf's `gqa` layers: the program's counters
`<layer>.masked` (layers/lm.py; static a layer, 1 on the XLA route,
which masks every tile), which the driver fetches after the window.
Only a tile that crosses the diagonal or a window's edge needs its mask;
the rest run plain. Silent where the window carries no such counter."""


def read(obs):
    counters = getattr(obs.window, "counters", None) or {}
    got = [counters[l.name + ".masked"] for l in obs.net.layers
           if l.type == "gqa" and l.name + ".masked" in counters]
    return sum(got) / len(got) if got else None
