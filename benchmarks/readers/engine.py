"""Engine counters (``LLMEngine.stats()``, differenced over the
window): a ratio of two counters, or the mean decode-batch occupancy as
a share of the decode slots."""


def read(obs, num=None, den=None, scale=1.0, occupancy=False):
    eng = obs.get("engine")
    if not eng:
        return None
    if occupancy:
        hist = eng["occupancy_hist"]
        steps = sum(hist.values())
        if not steps:
            return None
        mean = sum(int(k) * v for k, v in hist.items()) / steps
        return 100.0 * mean / obs["engine_config"]["decode_slots"]
    if not eng.get(den):
        return None
    return scale * eng[num] / eng[den]
