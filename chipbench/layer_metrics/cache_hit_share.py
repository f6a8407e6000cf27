"""Of the programs set-up asked the persistent cache for, the share it
had."""


def read(obs):
    c = obs["compile_setup"]
    if not c["requests"]:
        return None
    return 100.0 * c["hits"] / c["requests"]
