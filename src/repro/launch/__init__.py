"""Launchers: production mesh, conv mesh, fake devices, compile cache,
dry-run, drivers."""
from .devices import fake_devices, use_compile_cache  # noqa: F401
from .mesh import (  # noqa: F401
    make_conv_mesh,
    make_host_mesh,
    make_mesh,
    make_production_mesh,
)
