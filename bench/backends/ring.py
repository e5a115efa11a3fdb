"""The program's ``RingShardedBackend``: the KV cache sharded by sequence
position over the ``model`` axis of a ``("data", "model")`` mesh of shape
``serve.mesh`` over the run's devices, the weights replicated, each
decode row's query streamed around the ring in ``serve.mode`` (the
paper's queues as ``ppermute`` hops)."""


def build(cfg, scfg, params, conf, devices):
    from repro.launch.mesh import make_mesh
    from repro.serve.sharded_cache import RingShardedBackend
    s = conf["serve"]
    mesh = make_mesh(tuple(s["mesh"]), ("data", "model"), devices=devices)
    return RingShardedBackend(cfg, scfg, params, mesh, mode=s["mode"])
