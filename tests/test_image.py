"""Image pipeline: augmenters, ImageIter over RecordIO, im2rec, model_store
(reference layout: tests/python/unittest/test_image.py +
test_gluon_model_zoo.py)."""
import os
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import image, recordio

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _rand_img(h=36, w=42, c=3, seed=0):
    return onp.random.RandomState(seed).randint(
        0, 255, (h, w, c)).astype("uint8")


def test_imdecode_imencode_roundtrip_png():
    img = _rand_img()
    buf = image.imencode(img, fmt=".png")
    back = image.imdecode(buf)
    onp.testing.assert_array_equal(back.asnumpy(), img)


def test_resize_and_crops():
    img = mx.np.array(_rand_img())
    r = image.resize_short(img, 24)
    assert min(r.shape[:2]) == 24
    c, _ = image.center_crop(img, (20, 20))
    assert c.shape[:2] == (20, 20)
    rc, _ = image.random_crop(img, (16, 16))
    assert rc.shape[:2] == (16, 16)
    rsz, _ = image.random_size_crop(img, (20, 20), (0.5, 1.0), (0.9, 1.1))
    assert rsz.shape[:2] == (20, 20)


def test_create_augmenter_chain():
    augs = image.CreateAugmenter((3, 24, 24), resize=28, rand_crop=True,
                                 rand_mirror=True, mean=True, std=True,
                                 brightness=0.1, contrast=0.1,
                                 saturation=0.1, hue=0.1, pca_noise=0.1,
                                 rand_gray=0.1)
    out = mx.np.array(_rand_img())
    for a in augs:
        out = a(out)
    assert out.shape == (24, 24, 3)
    assert out.dtype == mx.np.float32
    for a in augs:
        assert a.dumps()  # serializable descriptions


def test_augmenter_determinism_flip():
    flip = image.HorizontalFlipAug(p=1.0)
    img = mx.np.array(_rand_img())
    onp.testing.assert_array_equal(flip(img).asnumpy(),
                                   img.asnumpy()[:, ::-1])


def _write_rec(prefix, n=6, size=32):
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    for i in range(n):
        img = _rand_img(size, size, seed=i)
        header = recordio.IRHeader(0, float(i % 3), i, 0)
        rec.write_idx(i, recordio.pack_img(header, img, img_fmt=".png"))
    rec.close()


def test_imageiter_over_recordio(tmp_path):
    prefix = str(tmp_path / "data")
    _write_rec(prefix)
    it = image.ImageIter(batch_size=4, data_shape=(3, 24, 24),
                         path_imgrec=prefix + ".rec",
                         aug_list=image.CreateAugmenter((3, 24, 24)))
    batch = next(iter(it))
    assert batch.data[0].shape == (4, 3, 24, 24)
    assert batch.label[0].shape == (4,)
    it.reset()
    batches = list(it)
    assert sum(4 - b.pad for b in batches) == 6


def test_im2rec_roundtrip(tmp_path):
    sys.path.insert(0, TOOLS)
    import im2rec
    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            buf = image.imencode(_rand_img(20, 20, seed=i), fmt=".png")
            with open(root / cls / f"{i}.png", "wb") as f:
                f.write(buf)
    prefix = str(tmp_path / "pack")
    classes = im2rec.make_list(prefix, str(root))
    assert classes == ["cat", "dog"]
    im2rec.pack(prefix, str(root))
    it = image.ImageIter(batch_size=2, data_shape=(3, 20, 20),
                         path_imgrec=prefix + ".rec",
                         aug_list=image.CreateAugmenter((3, 20, 20)))
    batch = next(iter(it))
    assert batch.data[0].shape == (2, 3, 20, 20)


@pytest.mark.slow
def test_model_store_cache_and_pretrained(tmp_path, monkeypatch):
    from mxnet_tpu.gluon.model_zoo import model_store
    from mxnet_tpu.gluon.model_zoo.vision import get_model

    # provision weights into the cache as a user would offline
    src = get_model("squeezenet1_0", classes=10)
    src.initialize()
    src(mx.np.zeros((1, 3, 64, 64)))
    root = tmp_path / "models"
    root.mkdir()
    src.save_parameters(str(root / "squeezenet1_0.params.npz"))

    net = get_model("squeezenet1_0", classes=10, pretrained=True,
                    root=str(root))
    a = src.collect_params()
    b = net.collect_params()
    for k in a:
        onp.testing.assert_array_equal(a[k].data().asnumpy(),
                                       b[k].data().asnumpy())


def test_model_store_missing_weights_actionable_error(tmp_path):
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet
    with pytest.raises(mx.MXNetError) as ei:
        get_resnet(1, 18, pretrained=True, root=str(tmp_path))
    msg = str(ei.value)
    assert "resnet18_v1" in msg and "params" in msg


def test_model_store_purge(tmp_path):
    from mxnet_tpu.gluon.model_zoo import model_store
    f = tmp_path / "x.params"
    f.write_bytes(b"abc")
    model_store.purge(str(tmp_path))
    assert not f.exists()


def test_apply_batch_matches_per_image_for_deterministic_chain():
    """Batch path == per-image path for deterministic augmenters."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import image as img

    rng = onp.random.RandomState(0)
    batch = rng.randint(0, 255, size=(4, 40, 48, 3)).astype("float32")
    chain = [img.ForceResizeAug((32, 24)), img.CastAug(),
             img.ColorNormalizeAug(onp.array([123.0, 117.0, 104.0]),
                                   onp.array([58.0, 57.0, 57.0]))]
    out = img.apply_batch(chain, batch).asnumpy()
    assert out.shape == (4, 24, 32, 3)
    for i in range(4):
        single = mx.np.array(batch[i])
        for aug in chain:
            single = aug(single)
        onp.testing.assert_allclose(out[i], single.asnumpy(),
                                    rtol=1e-4, atol=1e-3)


def test_batch_random_augs_shapes_and_bounds():
    import numpy as onp
    from mxnet_tpu import image as img

    rng = onp.random.RandomState(1)
    batch = rng.randint(0, 255, size=(8, 64, 64, 3)).astype("float32")
    chain = img.CreateAugmenter((3, 32, 32), rand_crop=True, rand_resize=True,
                                rand_mirror=True, brightness=0.2,
                                contrast=0.2, saturation=0.2, hue=0.1,
                                pca_noise=0.05, rand_gray=0.3,
                                mean=True, std=True)
    out = img.apply_batch(chain, batch).asnumpy()
    assert out.shape == (8, 32, 32, 3)
    assert onp.isfinite(out).all()
    # per-sample randomness: samples of identical input differ
    same = onp.repeat(batch[:1], 8, axis=0)
    out2 = img.apply_batch(chain, same).asnumpy()
    assert onp.abs(out2[0] - out2[1]).max() > 1e-3


def test_hue_rotation_preserves_gray_axis():
    """Rotating hue must fix gray pixels (the rotation axis)."""
    import numpy as onp
    from mxnet_tpu import image as img
    import jax

    gray = onp.full((2, 8, 8, 3), 128.0, "float32")
    aug = img.HueJitterAug(0.5)
    out = onp.asarray(aug.batch_apply(jax.numpy.asarray(gray),
                                      jax.random.PRNGKey(3)))
    onp.testing.assert_allclose(out, gray, rtol=1e-4)


def test_native_jpeg_decode_matches_pil():
    """native/mxtpu_decode.cc (libjpeg) must agree byte-for-byte with PIL
    (same underlying codec); batch path fans JPEGs over C threads."""
    pytest.importorskip("PIL")
    import io as _io

    from PIL import Image

    from mxnet_tpu import native
    if native.decode_lib() is None:
        pytest.skip("native decode lib unavailable")
    rng = onp.random.RandomState(0)
    bufs, refs = [], []
    for i in range(5):
        arr = (rng.rand(20 + i, 26, 3) * 255).astype(onp.uint8)
        b = _io.BytesIO()
        Image.fromarray(arr).save(b, format="JPEG", quality=95)
        bufs.append(b.getvalue())
        refs.append(onp.asarray(Image.open(
            _io.BytesIO(b.getvalue())).convert("RGB")))
    # PIL wheels bundle their own libjpeg-turbo; the system libjpeg may
    # round the IDCT differently by +-1 per pixel — that's the contract
    one = native.jpeg_decode(bufs[0])
    onp.testing.assert_allclose(one.astype(int), refs[0].astype(int),
                                atol=1)
    gray = native.jpeg_decode(bufs[0], gray=True)
    assert gray.shape == refs[0].shape[:2] + (1,)
    batch = image.imdecode_batch_np(bufs)
    for got, want in zip(batch, refs):
        onp.testing.assert_allclose(got.astype(int), want.astype(int),
                                    atol=1)
    # non-JPEG payloads fall back to the generic path inside the batch API
    npy = _io.BytesIO()
    onp.save(npy, refs[0])
    mixed = image.imdecode_batch_np([bufs[0], npy.getvalue()])
    onp.testing.assert_array_equal(mixed[1], refs[0])
    # corrupt JPEG magic inside a batch: no crash, PIL path raises cleanly
    with pytest.raises(Exception):
        image.imdecode_batch_np([b"\xff\xd8garbage"])
