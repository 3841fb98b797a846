"""A number the run observed directly: ``path`` into the observations,
times ``scale``; with ``over`` (another path), their ratio."""


def _get(obs, path):
    for key in path:
        if not isinstance(obs, dict) or obs.get(key) is None:
            return None
        obs = obs[key]
    return obs


def read(obs, path, scale=1.0, over=None):
    value = _get(obs, path)
    if value is None:
        return None
    if over is not None:
        den = _get(obs, over)
        if not den:
            return None
        value = value / den
    return scale * value
