"""Clean twin of bad_branding.py: branding passed explicitly (None is fine)."""


def threads(self, session, batch, plan, child, condition, key, **kw):
    from hyperspace_tpu.exec.device import device_filter_mask, stage_filter_columns

    mask = self._filter_mask(plan, child, kept=None)
    m2 = device_filter_mask(session, batch, condition, scan_key=key)
    stage_filter_columns(session, batch, condition, key)  # positional is fine
    m3 = device_filter_mask(session, batch, condition, **kw)  # forwarded
    return mask, m2, m3
