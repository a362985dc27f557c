"""The application layer (ABCI boundary).

App/Context re-exports are LAZY (PEP 562): `app.app` pulls the full
state-machine import chain (crypto, x/ modules), so importing a light
submodule of the package must not drag it in.
"""

_EXPORTS = {
    "App": ("celestia_tpu.app.app", "App"),
    "GENESIS_CHAIN_ID": ("celestia_tpu.app.app", "GENESIS_CHAIN_ID"),
    "Context": ("celestia_tpu.app.context", "Context"),
    "GasMeter": ("celestia_tpu.app.context", "GasMeter"),
    "OutOfGasError": ("celestia_tpu.app.context", "OutOfGasError"),
}


def __getattr__(name: str):
    try:
        module, attr = _EXPORTS[name]
    except KeyError:
        raise AttributeError(name) from None
    import importlib

    return getattr(importlib.import_module(module), attr)


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
