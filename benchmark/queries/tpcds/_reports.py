"""What the three reporting queries (Q67, Q36, Q89) share: the join of
``store_sales`` with a filtered ``date_dim``, ``item`` and ``store``, on the
engine and in pandas, and the twins' row makers.  Inner joins: a fact row
whose foreign key is null (about 4% a key) matches nothing, in both."""

YEARS = [1998, 2002]   # the years the generator's sales fall in


def star(dfs, date_pred, item_pred=None, store_pred=None):
    item, store = dfs["item"], dfs["store"]
    if item_pred is not None:
        item = item.filter(item_pred)
    if store_pred is not None:
        store = store.filter(store_pred)
    return (dfs["store_sales"]
            .join(dfs["date_dim"].filter(date_pred),
                  on=[("ss_sold_date_sk", "d_date_sk")])
            .join(item, on=[("ss_item_sk", "i_item_sk")])
            .join(store, on=[("ss_store_sk", "s_store_sk")]))


def star_pandas(pds, date_mask, item_mask=None, store_mask=None):
    ss, d, i, s = (pds[t] for t in ("store_sales", "date_dim", "item",
                                    "store"))
    if item_mask is not None:
        i = i[item_mask(i)]
    if store_mask is not None:
        s = s[store_mask(s)]
    return (ss.merge(d[date_mask(d)], left_on="ss_sold_date_sk",
                     right_on="d_date_sk")
            .merge(i, left_on="ss_item_sk", right_on="i_item_sk")
            .merge(s, left_on="ss_store_sk", right_on="s_store_sk"))


def rollup_pandas(m, keys, sums):
    """GROUP BY ROLLUP(keys) as len(keys)+1 plain group-bys, one a grouping
    set, concatenated with the keys outside the set NULL; ``level`` is the
    number of keys aggregated away (0 = the full set).  ``sums``: {output
    column: float64 input column}.  The data has no NULL in any key (the
    dimensions' columns are never null, the fact's null keys fall to the
    inner joins), so a NULL key in the output is the set's."""
    import pandas as pd
    if not len(m):
        return pd.DataFrame(columns=list(keys) + list(sums) + ["level"])
    parts = []
    for k in range(len(keys), -1, -1):
        if k:
            g = (m.groupby(list(keys[:k]), sort=False)[list(sums.values())]
                 .sum().reset_index())
        else:
            g = m[list(sums.values())].sum().to_frame().T
        g = g.rename(columns={v: out for out, v in sums.items()})
        for name in keys[k:]:
            g[name] = None
        g["level"] = len(keys) - k
        parts.append(g[list(keys) + list(sums) + ["level"]])
    return pd.concat(parts, ignore_index=True)


def cell(v):
    """A pandas cell as the engine returns it: None for a null, python
    ints for whole numbers that a NULL beside them turned into floats."""
    import numpy as np
    if v is None or (isinstance(v, float) and v != v):
        return None
    if isinstance(v, (np.integer,)):
        return int(v)
    return v
