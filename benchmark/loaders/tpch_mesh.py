"""TPC-H tables loaded into a deployment whose tables are row-sharded over a
device mesh.

The layout is stated the way an operator states it, in SQL on the session
the harness hands over: ``SET GLOBAL mesh_devices = N`` (the configuration's
``scale.mesh_devices``).  From then on every connection the server accepts
runs its SELECTs as one program over the N devices.  A program that does not
know the setting refuses the statement, and the run ends there.  The tables
and their load are ``benchmark.loaders.tpch``'s, unchanged.
"""

from benchmark.loaders import tpch


def load(config: dict, seed: int, scale: float, session) -> dict:
    session.execute(
        f"SET GLOBAL mesh_devices = {int(config['scale']['mesh_devices'])}")
    return tpch.load(config, seed, scale, session)
