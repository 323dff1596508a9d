"""Architectures, one module each, found by the name in a configuration's
``"arch"`` key (``spec.arch(conf)``).  A new architecture is a new file
here; nothing lists them.

A module gives:

``make_params(model, seed)``
    The weights of the configuration's ``model`` from ``seed``, made on the
    device in one jitted call, as the server takes them.
``make_engine(conf, params)``
    The system under test: a ``repro.segserve.SegEngine`` serving the
    configuration with ``params``.
``forward(params, x, planes, *, bits=8)``
    The plain reference: (N, H, W, C) float32 windows to (N, H, W,
    ``model["n_classes"]``) logits at the budgets ``planes`` (one per
    layer of the plane schedule), computing what the configuration states
    and importing nothing of the program; ``bits=4`` is the control.
``canvas_shape(h, w, conf)``, ``canvas(image, conf)``, ``plan(h, w, conf)``
    The padded canvas of an ``h x w`` image, the image on it, and the
    ``geometry.Tile``s the server runs for it (one whole-image tile where
    the architecture cannot be tiled exactly).
``layers(model, n, h, w)``
    The layers of one forward over ``n`` windows of ``h x w``, each with
    ``.ops`` (two per multiply-add) and ``.bytes`` (least traffic):
    ``mfu`` and ``mma_roofline`` count their work from these.
``tiny(conf, sizes)``
    ``conf`` cut to the sizes of ``tests/chipbench/tiny/<config>.json``,
    for the CPU rehearsals of the tests.

Only ``make_engine`` (and what it calls) may import the program, and only
inside its body: the module's top level, ``make_params``, ``forward``, the
tile plan and ``layers`` import nothing of it, so that ``correct`` compares
the program with something it did not compute.  The tests run each of them
with ``repro`` blocked from import.
"""
