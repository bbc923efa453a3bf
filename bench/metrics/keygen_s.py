"""Host seconds of key generation (secret, relinearisation and rotation keys)."""


def read(run):
    return run.get("phases", {}).get("keygen_s")
