"""Operations a training step has to do, counted from the conf's shapes.

Required work only: 2 FLOP for each multiply-add of every conv and
fullc layer, once forward, once for the weight gradient and once for
the data gradient - except that a layer fed by the input node needs no
data gradient. Pooling, LRN, batch-norm, activations and the updater
are not counted (they are the memory-bound part; `step_mfu` is the
share of the MXU peak that the required matrix work reaches). Nothing a
program recomputes counts.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmark import confnet


def layer_macs(net: confnet.Net) -> List[Tuple[str, int, bool]]:
    """(layer name, forward multiply-adds per image, fed by the input
    node) for each conv and fullc layer."""
    rows = []
    for lay in net.layers:
        if lay.type == "conv":
            cin = lay.in_shapes[0][0]
            k = lay.kernel()
            cout, oh, ow = lay.out_shape
            macs = cout * oh * ow * (cin // lay.group()) * k * k
        elif lay.type == "fullc":
            macs = lay.in_shapes[0][0] * lay.out_shape[0]
        else:
            continue
        rows.append((lay.name, macs, lay.ins[0] in ("0", "in")))
    return rows


def forward_macs_per_image(net: confnet.Net) -> int:
    return sum(m for _, m, _ in layer_macs(net))


def train_flop_per_image(net: confnet.Net) -> int:
    return sum(2 * m * (2 if first else 3) for _, m, first in layer_macs(net))


def counts(net: confnet.Net) -> Dict[str, int]:
    return {"forward_macs_per_image": forward_macs_per_image(net),
            "train_flop_per_image": train_flop_per_image(net)}
